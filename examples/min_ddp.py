"""min_ddp — the reference workload, TPU-native.

Behavioral mirror of the reference's ``min_DDP.py`` (see SURVEY.md §2.2/§3):
same CLI flags and defaults, same seeded dataset, same model shape and
optimizer, same per-rank and cross-rank printed metrics, same graceful
0/1/N-device degradation — but the training step is ONE compiled XLA
program (forward → backward → gradient all-reduce over ICI → AdamW update →
metrics), instead of an eager loop with four separate collectives per
iteration (reference ``min_DDP.py:95-130``).

Run:  python examples/min_ddp.py --epochs 2 --batch-size 8
(on a CPU-only host, JAX_PLATFORMS=cpu DPX_CPU_DEVICES=8 with
jax.config.update("jax_num_cpu_devices", 8) before the first backend use
gives a virtual 8-device mesh; on TPU the chips are discovered
automatically.)
"""

import argparse
import os
import sys

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import distributed_pytorch_tpu as dist
from distributed_pytorch_tpu import models, optim
from distributed_pytorch_tpu.data import DataLoader, DummyDataset
from distributed_pytorch_tpu.ops.losses import cross_entropy_per_example
from distributed_pytorch_tpu.parallel import make_train_step


def parse_args(argv=None):
    # Same five flags/defaults as the reference (min_DDP.py:10-24).
    parser = argparse.ArgumentParser(description="TPU Multi-Chip Training")
    parser.add_argument("--epochs", default=2, type=int, metavar="N",
                        help="Number of training epochs.")
    parser.add_argument("--batch-size", default=8, type=int, metavar="N",
                        help="Per-rank batch size.")
    parser.add_argument("--n-classes", default=4, type=int, metavar="N",
                        help="Number of classes for fake dataset.")
    parser.add_argument("--data-size", default=32, type=int, metavar="N",
                        help="Size of fake dataset.")
    parser.add_argument("--hidden-dim", default=32, type=int, metavar="N",
                        help="Hidden dimension.")
    return parser.parse_args(argv)


def main_worker(rank, world_size, argv=None, quiet=False, history=None):
    """Per-controller program — the reference's ``main_worker``
    (``min_DDP.py:53-89``). ``history`` (a list) collects the reduced loss
    per step when provided, for parity tests."""
    is_distributed = world_size > 1
    if is_distributed:
        dist.init_process_group(rank, world_size)

    args = parse_args(argv)
    if not quiet:
        for name, val in vars(args).items():
            dist.print_primary("{:<12}: {}".format(name, val))

    # Data — seeded identically everywhere (reference min_DDP.py:27-38,63-66)
    dataset = DummyDataset(args.data_size, args.n_classes)
    sampler = dist.data_sampler(dataset, is_distributed, shuffle=False)
    loader = DataLoader(dataset, batch_size=args.batch_size,
                        shuffle=(sampler is None), sampler=sampler)

    # Model — replicated params are the DDP ctor broadcast (min_DDP.py:69-71)
    model = models.DummyModel(in_dim=1, hidden_dim=args.hidden_dim,
                              n_classes=args.n_classes)
    params = model.init(jax.random.PRNGKey(0))
    params = dist.replicate(params)

    # Optimizer and loss (min_DDP.py:74-75)
    optimizer = optim.adamw(0.0001)

    def loss_fn(p, batch):
        x, y = batch
        logits = model.apply(p, x)
        per_ex = cross_entropy_per_example(logits, y)
        preds = jax.numpy.argmax(logits, axis=-1)
        correct = (preds == y)
        return per_ex.mean(), {"correct": correct, "preds": preds}

    step_fn = make_train_step(loss_fn, optimizer)
    # sharded weight update (DPX_WEIGHT_UPDATE=sharded): the step owns
    # its flat 1/world state layout; replicated keeps optimizer.init
    opt_state = (step_fn.init_opt_state(params)
                 if hasattr(step_fn, "init_opt_state")
                 else dist.replicate(optimizer.init(params)))

    if not quiet:
        print("Run epochs")
    for epoch in range(args.epochs):
        dist.print_primary(f"------- Epoch {epoch + 1}")
        if is_distributed:
            sampler.set_epoch(epoch)
        params, opt_state = train(step_fn, params, opt_state, loader,
                                  world_size, args.batch_size, quiet, history)

    dist.cleanup()
    return params


def train(step_fn, params, opt_state, loader, world_size, batch_size,
          quiet=False, history=None):
    """One epoch — the reference's ``train`` loop (``min_DDP.py:92-130``),
    with forward/backward/all-reduce/update fused into ``step_fn`` and the
    prints kept at the step boundary."""
    world = max(world_size, 1)
    for it, (x, y) in enumerate(loader):
        batch = dist.shard_batch((x, y))

        params, opt_state, loss, metrics = step_fn(params, opt_state, batch)

        # Per-rank diagnostics (reference min_DDP.py:110-116). loss is
        # stacked (world,), metrics are global arrays in rank order.
        if not quiet:
            correct = np.asarray(metrics["correct"])
            preds = np.asarray(metrics["preds"])
            losses = np.asarray(loss)
            xs = np.asarray(x).reshape(world, -1)
            ys = np.asarray(y).reshape(world, -1)
            b = xs.shape[1]
            for r in range(world):
                sl = slice(r * b, (r + 1) * b)
                corr = correct[sl]
                print(f"Device: {dist.get_device() if world == 1 else f'mesh[{r}]'}"
                      f"\n\tInput: \t{xs[r].astype(np.uint8)}"
                      f"\n\tLabel: \t{ys[r]}"
                      f"\n\tPred:  \t{preds[sl]}"
                      f"\n\tCorr.: \t{corr.astype(np.uint8)}"
                      f"\n\tAcc:   \t{corr.sum() / b:.5f} ({corr.sum()}/{b})"
                      f"\n\tLoss:  \t{losses[r]:.5f}")

        # Barrier before cross-rank metric sync (reference min_DDP.py:119)
        dist.wait_for_everyone()

        # Cross-rank metrics (reference min_DDP.py:122-130). reduce is SUM —
        # the reference's comment says average but its op is SUM
        # (SURVEY.md §3.3 quirk) — and gather feeds global accuracy.
        loss_red = dist.reduce(loss)
        correct_g = dist.gather(
            np.asarray(metrics["correct"]).reshape(world, -1))
        correct_all = np.concatenate([np.asarray(c) for c in correct_g])
        acc = correct_all.sum() / correct_all.size

        loss_val = float(np.asarray(loss_red).reshape(-1)[0])
        if history is not None:
            history.append(loss_val)
        if not quiet:
            dist.print_primary(
                f"Finish iteration {it}"
                f" - acc: {acc:.4f} ({correct_all.sum()}/{correct_all.size})"
                f" - loss: {loss_val:.4f}")
    return params, opt_state


if __name__ == "__main__":
    # code that should only execute once goes here (reference min_DDP.py:133-139)
    dist.launch(main_worker)
