"""ResNet-18 image-classification training — the vision rung of the
evaluation ladder (BASELINE.json: ResNet-18 on CIFAR-10).

Zero-egress data policy: if ``--data-dir`` points at an extracted
``cifar-10-batches-py`` directory (the standard CIFAR-10 python pickle
layout) it trains on real CIFAR-10 read directly with numpy; otherwise it
falls back to the seeded synthetic CIFAR-shaped dataset. Same model and
step code either way.

BatchNorm running stats follow torch-DDP semantics (per-device, unsynced)
via the stateful DP step. NHWC layout throughout (nn/conv.py).

Run:  python examples/train_resnet.py --epochs 2 --batch-size 64
"""

import argparse
import os
import pickle
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import distributed_pytorch_tpu as dist
from distributed_pytorch_tpu import models, optim
from distributed_pytorch_tpu.data import DataLoader, SyntheticImages
from distributed_pytorch_tpu.ops.losses import cross_entropy_per_example
from distributed_pytorch_tpu.parallel import make_stateful_train_step
from distributed_pytorch_tpu.utils import MetricsLogger


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="TPU ResNet-18 training")
    p.add_argument("--epochs", default=2, type=int)
    p.add_argument("--batch-size", default=64, type=int,
                   help="Per-rank batch size.")
    p.add_argument("--lr", default=0.05, type=float)
    p.add_argument("--momentum", default=0.9, type=float)
    p.add_argument("--data-dir", default=None, type=str,
                   help="Path containing cifar-10-batches-py (no download "
                        "is attempted); default: synthetic images.")
    p.add_argument("--data-size", default=2048, type=int,
                   help="Synthetic dataset size when --data-dir is unset.")
    p.add_argument("--bf16", action="store_true")
    p.add_argument("--sync-bn", action="store_true",
                   help="Cross-replica BatchNorm statistics over the dp "
                        "axis (torch nn.SyncBatchNorm); default matches "
                        "torch DDP's per-device BN.")
    p.add_argument("--limit-steps", default=None, type=int,
                   help="Cap steps per epoch (smoke runs).")
    p.add_argument("--ema", default=0.0, type=float, metavar="DECAY",
                   help="Track an EMA of the weights (optim.with_ema) "
                        "and report eval accuracy with both raw and "
                        "averaged weights. Caveat: BN running stats come "
                        "from the raw trajectory, so the EMA number "
                        "understates until stats are re-estimated "
                        "(torch swa_utils.update_bn has the same issue).")
    p.add_argument("--eval", action="store_true",
                   help="Evaluate after each epoch on the held-out split "
                        "(CIFAR test_batch, or 10%% of synthetic data).")
    p.add_argument("--log", default=None, type=str)
    return p.parse_args(argv)


class Cifar10:
    """CIFAR-10 train split from the standard python pickle batches,
    read with numpy alone. NHWC float32 in [0,1], per-channel normalized."""

    MEAN = np.array([0.4914, 0.4822, 0.4465], np.float32)
    STD = np.array([0.2470, 0.2435, 0.2616], np.float32)

    def __init__(self, root: str, split: str = "train"):
        d = os.path.join(root, "cifar-10-batches-py")
        if not os.path.isdir(d):
            raise FileNotFoundError(f"{d} not found")
        files = ([f"data_batch_{i}" for i in range(1, 6)]
                 if split == "train" else ["test_batch"])
        xs, ys = [], []
        for name in files:
            with open(os.path.join(d, name), "rb") as f:
                batch = pickle.load(f, encoding="bytes")
            xs.append(batch[b"data"])
            ys.extend(batch[b"labels"])
        x = np.concatenate(xs).reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
        x = x.astype(np.float32) / 255.0
        self.images = (x - self.MEAN) / self.STD
        self.labels = np.asarray(ys, np.int32)

    def __getitem__(self, i):
        return self.images[i], self.labels[i]

    def __len__(self):
        return len(self.labels)


def main_worker(rank, world_size, argv=None, quiet=False, history=None):
    is_distributed = world_size > 1
    if is_distributed:
        dist.init_process_group(rank, world_size)
    args = parse_args(argv)
    if not quiet:
        for name, val in vars(args).items():
            dist.print_primary("{:<12}: {}".format(name, val))

    if args.data_dir:
        dataset = Cifar10(args.data_dir)
        eval_set = Cifar10(args.data_dir, split="test") if args.eval else None
    else:
        dataset = SyntheticImages(args.data_size)
        eval_set = (SyntheticImages(max(args.data_size // 10,
                                        args.batch_size * max(world_size, 1)),
                                    seed=1)
                    if args.eval else None)
    sampler = dist.data_sampler(dataset, is_distributed, shuffle=True)
    loader = DataLoader(dataset, batch_size=args.batch_size,
                        shuffle=(sampler is None), sampler=sampler,
                        drop_last=True)
    if len(loader) == 0:
        raise ValueError(
            f"batch size {args.batch_size} x {max(world_size, 1)} ranks "
            f"exceeds the {len(dataset)}-sample dataset (drop_last): "
            "no full batch to train on")

    model = models.ResNet18(n_classes=10, small_input=True,
                            sync_bn=args.sync_bn)
    params, state = model.init(jax.random.PRNGKey(0))
    if args.bf16:
        params = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16)
            if x.dtype == jnp.float32 else x, params)
    optimizer = optim.sgd(args.lr, momentum=args.momentum)
    if args.ema:
        # the averaged weights live in the optimizer state: updated
        # inside the compiled step, checkpointed/sharded with it
        optimizer = optim.with_ema(optimizer, decay=args.ema)

    params = dist.replicate(params)
    opt_state = dist.replicate(optimizer.init(params))
    world = max(world_size, 1)
    if world > 1:
        # per-device BN stats: stack state on a leading device axis
        from distributed_pytorch_tpu.parallel import stack_state
        state = stack_state(state, world)
    state = dist.shard_batch(state) if world > 1 else jax.device_put(state)

    def loss_fn(p, st, batch):
        x, y = batch
        logits, new_st = model.apply(p, x.astype(
            jnp.bfloat16 if args.bf16 else jnp.float32), state=st,
            train=True)
        per_ex = cross_entropy_per_example(logits, y)
        correct = (jnp.argmax(logits, axis=-1) == y)
        return per_ex.mean(), (new_st, {"correct": correct})

    step_fn = make_stateful_train_step(loss_fn, optimizer)

    eval_step = eval_loader = None
    if eval_set is not None:
        from distributed_pytorch_tpu.parallel import make_stateful_eval_step

        eval_sampler = dist.data_sampler(eval_set, is_distributed,
                                         shuffle=False)
        eval_loader = DataLoader(eval_set, batch_size=args.batch_size,
                                 sampler=eval_sampler, drop_last=True)

        def eval_fn(p, st, batch):
            x, y = batch
            logits, _ = model.apply(p, x.astype(
                jnp.bfloat16 if args.bf16 else jnp.float32), state=st,
                train=False)
            return (jnp.argmax(logits, axis=-1) == y)

        eval_step = make_stateful_eval_step(eval_fn)

    logger = MetricsLogger(args.log)

    # Host syncs only at epoch boundaries: losses and correct-counts are
    # accumulated as (lazy) device values so steps pipeline on the chip —
    # a per-step host read costs a full round trip.
    t_run0 = None
    timed_steps = 0
    for epoch in range(args.epochs):
        loader.set_epoch(epoch)
        dev_losses = []
        dev_correct = []
        n_seen = 0
        for it, batch in enumerate(loader):
            if args.limit_steps is not None and it >= args.limit_steps:
                break
            out = step_fn(params, state, opt_state, dist.shard_batch(batch))
            params, state, opt_state = (out.params, out.state,
                                        out.opt_state)
            dev_losses.append(out.loss)
            dev_correct.append(out.metrics["correct"].sum())
            n_seen += world * args.batch_size
            if epoch == 0 and it == 0:
                jax.block_until_ready(out.loss)  # past compile
                t_run0 = time.perf_counter()
            else:
                timed_steps += 1
        losses = [float(np.asarray(l).mean()) for l in dev_losses]
        correct_sum = int(sum(int(np.asarray(c)) for c in dev_correct))
        if history is not None:
            history.extend(losses)
        for i, l in enumerate(losses):
            logger.log(epoch * len(loader) + i, loss=l)
        if not quiet:
            dist.print_primary(
                f"epoch {epoch}: acc {correct_sum / max(n_seen, 1):.4f} "
                f"loss {losses[-1]:.4f}")
        if eval_step is not None:
            weight_sets = [("", params)]
            if args.ema:
                weight_sets.append(
                    ("ema_", optim.ema_params(opt_state, like=params)))
            for tag, w in weight_sets:
                evs = [eval_step(w, state, dist.shard_batch(b))
                       for b in eval_loader]
                corr = np.concatenate([np.asarray(e).reshape(-1)
                                       for e in evs])
                logger.log(epoch, **{f"{tag}eval_acc": corr.mean()})
                if not quiet:
                    dist.print_primary(
                        f"epoch {epoch}: EVAL{' (ema)' if tag else ''} "
                        f"acc {corr.mean():.4f} "
                        f"({int(corr.sum())}/{corr.size})")

    jax.block_until_ready(params)
    if t_run0 is not None and timed_steps > 0 and not quiet:
        sps = timed_steps / (time.perf_counter() - t_run0)
        dist.print_primary(
            f"done: {sps:.2f} steps/s, "
            f"{sps * world * args.batch_size:,.0f} images/s")
    logger.close()
    dist.cleanup()
    return params


if __name__ == "__main__":
    dist.launch(main_worker)
