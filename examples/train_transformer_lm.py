"""Transformer-LM training — the language-model rung of the evaluation
ladder (BASELINE.json: "nn.TransformerEncoder LM on WikiText-2", built here
as a decoder-only causal LM).

Zero-egress data policy: trains on a local text corpus byte-tokenized
(``--text /path/to/corpus`` — a file, or a directory like the Python
stdlib source tree whose text files are concatenated) or, by default, the
seeded synthetic LM dataset — same model/step code either way.

Showcases the TPU-native fast paths on top of the reference-parity API:
  --flash      pallas flash-attention core instead of the dense einsum
  --bf16       bfloat16 params/activations (f32 softmax/loss stats)
  --fsdp       ZeRO-3 layout over the dp axis (params/grads/moments sharded)
  --trace DIR  XProf device trace of a few steps

Run:  python examples/train_transformer_lm.py --steps 50 --flash --bf16
"""

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import distributed_pytorch_tpu as dist
from distributed_pytorch_tpu import models, optim
from distributed_pytorch_tpu.data import (DataLoader, SyntheticLM,
                                          device_prefetch)
from distributed_pytorch_tpu.ops import make_flash_attn_fn
from distributed_pytorch_tpu.ops.losses import cross_entropy_per_example
from distributed_pytorch_tpu.parallel import (fsdp_param_specs,
                                              make_fsdp_train_step,
                                              make_train_step,
                                              shard_batch_spec,
                                              shard_model_and_opt)
from distributed_pytorch_tpu.runtime import context
from distributed_pytorch_tpu.utils import MetricsLogger, profiler
from jax.sharding import PartitionSpec as P


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="TPU Transformer-LM training")
    p.add_argument("--steps", default=100, type=int,
                   help="Total training steps (across epochs of the data).")
    p.add_argument("--batch-size", default=8, type=int,
                   help="Per-rank batch size.")
    p.add_argument("--seq-len", default=256, type=int)
    p.add_argument("--dim", default=256, type=int)
    p.add_argument("--n-layers", default=4, type=int)
    p.add_argument("--n-heads", default=8, type=int)
    p.add_argument("--n-kv-heads", default=None, type=int,
                   help="grouped-query attention: kv heads < n-heads "
                        "(shrinks kv projections and the decode KV cache)")
    p.add_argument("--tie-embeddings", action="store_true",
                   help="share the token table with the vocab projection "
                        "(GPT-2 recipe; removes the head matrix)")
    p.add_argument("--pos", default="learned",
                   choices=["learned", "rope", "none"],
                   help="positional scheme: learned absolute table or "
                        "rotary embeddings (RoPE, parameter-free)")
    p.add_argument("--lr", default=None, type=float,
                   help="default: 3e-4 for adamw and adamw8bit; unset "
                        "for adafactor, which then uses its canonical "
                        "relative-step mode min(1e-2, 1/sqrt(t)) * "
                        "RMS(param)")
    p.add_argument("--optimizer", default="adamw",
                   choices=["adamw", "adafactor", "adamw8bit"],
                   help="adafactor: factored second moments, O(rows+cols) "
                        "optimizer memory (optim.adafactor); adamw8bit: "
                        "blockwise-int8 moments, ~1/4 the state bytes "
                        "(optim.adamw_8bit)")
    p.add_argument("--warmup-steps", default=0, type=int,
                   help="Linear warmup into cosine decay over --steps "
                        "(the standard LM schedule); 0 = constant lr.")
    p.add_argument("--clip-norm", default=0.0, type=float,
                   help="Clip gradients by global L2 norm; 0 = off.")
    p.add_argument("--text", default=None, type=str,
                   help="Local text file OR directory to byte-tokenize "
                        "(vocab=256; a directory concatenates its "
                        ".py/.md/.txt/.rst files up to a 64MiB cap, "
                        "e.g. the Python stdlib source tree); default: "
                        "seeded synthetic tokens.")
    p.add_argument("--data-size", default=512, type=int,
                   help="Number of synthetic samples when --text is unset.")
    p.add_argument("--flash", action="store_true",
                   help="Use the pallas flash-attention kernel.")
    p.add_argument("--bf16", action="store_true")
    p.add_argument("--fsdp", action="store_true",
                   help="ZeRO-3 layout instead of replicated DP.")
    p.add_argument("--fused-ce", action="store_true",
                   help="stream the vocab projection through "
                        "fused_linear_cross_entropy: the (B,S,vocab) logits "
                        "never materialize (frees HBM for batch/seq)")
    p.add_argument("--remat", action="store_true",
                   help="Rematerialize each block in backward (less "
                        "activation memory, ~1/3 more FLOPs).")
    p.add_argument("--master-f32", action="store_true",
                   help="With --bf16: keep float32 master weights in the "
                        "optimizer state (standard mixed-precision recipe; "
                        "raw bf16 params drop updates smaller than ~2^-8 "
                        "of the weight).")
    p.add_argument("--trace", default=None, type=str,
                   help="Capture an XProf trace of steps 5-10 into DIR.")
    p.add_argument("--log", default=None, type=str,
                   help="Line-JSON metrics file.")
    p.add_argument("--prefetch", default=0, type=int, metavar="N",
                   help="Prefetch N batches onto device from a background "
                        "thread (batch assembly and H2D overlap "
                        "compute).")
    p.add_argument("--log-every", default=10, type=int,
                   help="Steps between host syncs (loss fetch + log). "
                        "Between boundaries the loop never blocks, so "
                        "steps pipeline on the device.")
    p.add_argument("--generate", default=0, type=int, metavar="N",
                   help="After training, greedy-decode N tokens from a "
                        "short prompt with the compiled KV-cache path "
                        "and print them (byte-decoded when --text).")
    p.add_argument("--eval", action="store_true",
                   help="Hold out 10%% of the data; report validation "
                        "loss and perplexity after training.")
    p.add_argument("--save", default=None, type=str, metavar="DIR",
                   help="Checkpoint directory (atomic, retention-managed; "
                        "utils/checkpoint.py).")
    p.add_argument("--save-every", default=50, type=int,
                   help="Steps between checkpoints when --save is set.")
    p.add_argument("--sharded-ckpt", action="store_true",
                   help="Sharded checkpoints (ckpt/): every host writes "
                        "only the shards it owns per the FSDP specs, "
                        "restores reshard onto any world size, and async "
                        "saves defer their commit barrier to the main "
                        "thread instead of degrading to sync.")
    p.add_argument("--resume", action="store_true",
                   help="Restore the latest checkpoint from --save and "
                        "continue (exact continuation: the data stream "
                        "fast-forwards to the saved step).")
    p.add_argument("--elastic", default=0, type=int, metavar="N",
                   help="Supervise training in a child process and "
                        "relaunch up to N times on failure, resuming "
                        "from the latest --save checkpoint "
                        "(runtime/elastic.py; requires --save).")
    return p.parse_args(argv)


class Subset:
    """Index-selected view of a dataset (the holdout split)."""

    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = np.asarray(indices)

    def __getitem__(self, i):
        return self.dataset[int(self.indices[i])]

    def __len__(self):
        return len(self.indices)


class ByteCorpus:
    """Byte-level LM windows over a local text corpus: sample i is
    (bytes[i*S:(i+1)*S], shifted-by-one targets).

    ``path`` may be a file, or a directory whose ``.py/.md/.txt/.rst``
    files (sorted, recursive) are concatenated up to ``max_bytes``
    (default 64 MiB; truncation is reported on stderr) — e.g. the Python
    stdlib source tree, the only sizeable real text corpus in a
    zero-egress environment."""

    _EXTS = (".py", ".md", ".txt", ".rst")

    def __init__(self, path: str, seq_len: int, max_bytes: int = 1 << 26):
        if os.path.isdir(path):
            chunks, total = [], 0
            for root, dirs, files in os.walk(path):
                if total >= max_bytes:
                    break
                dirs.sort()
                for f in sorted(files):
                    if total >= max_bytes:
                        break
                    if f.endswith(self._EXTS):
                        try:
                            chunk = np.fromfile(os.path.join(root, f),
                                                dtype=np.uint8,
                                                count=max_bytes - total)
                        except OSError:
                            continue
                        chunks.append(chunk)
                        total += len(chunk)
            if not chunks:
                raise ValueError(f"{path}: no text files found")
            if total >= max_bytes:
                print(f"ByteCorpus: {path} truncated to {max_bytes} bytes "
                      f"(max_bytes cap)", file=sys.stderr)
            raw = np.concatenate(chunks)
        else:
            raw = np.fromfile(path, dtype=np.uint8)
        n = (len(raw) - 1) // seq_len
        if n < 1:
            raise ValueError(f"{path}: need > {seq_len + 1} bytes")
        self.x = raw[: n * seq_len].reshape(n, seq_len).astype(np.int32)
        self.y = raw[1 : n * seq_len + 1].reshape(n, seq_len).astype(np.int32)

    def __getitem__(self, i):
        return self.x[i], self.y[i]

    def __len__(self):
        return len(self.x)


def main_worker(rank, world_size, argv=None, quiet=False, history=None):
    is_distributed = world_size > 1
    if is_distributed:
        dist.init_process_group(rank, world_size)
    args = parse_args(argv)
    if not quiet:
        for name, val in vars(args).items():
            dist.print_primary("{:<12}: {}".format(name, val))

    vocab = 256
    if args.text:
        dataset = ByteCorpus(args.text, args.seq_len)
    else:
        dataset = SyntheticLM(args.data_size, args.seq_len, vocab)
    eval_set = None
    if args.eval:
        n = len(dataset)
        n_eval = max(n // 10, 1)
        dataset, eval_set = (Subset(dataset, np.arange(n - n_eval)),
                             Subset(dataset, np.arange(n - n_eval, n)))
    sampler = dist.data_sampler(dataset, is_distributed, shuffle=True)
    loader = DataLoader(dataset, batch_size=args.batch_size,
                        shuffle=(sampler is None), sampler=sampler,
                        drop_last=True)
    if len(loader) == 0:
        raise ValueError(
            f"batch size {args.batch_size} x {max(world_size, 1)} ranks "
            f"exceeds the {len(dataset)}-sample dataset (drop_last): "
            "no full batch to train on")

    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    attn_fn = make_flash_attn_fn() if args.flash else None
    model = models.TransformerLM(vocab=vocab, dim=args.dim,
                                 n_layers=args.n_layers,
                                 n_heads=args.n_heads,
                                 n_kv_heads=args.n_kv_heads, pos=args.pos,
                                 tie_embeddings=args.tie_embeddings,
                                 max_seq=args.seq_len, attn_fn=attn_fn,
                                 remat=args.remat, dtype=dtype)
    params = model.init(jax.random.PRNGKey(0))
    if args.warmup_steps >= args.steps > 0:
        raise ValueError(
            f"--warmup-steps {args.warmup_steps} must be < --steps "
            f"{args.steps} (the cosine phase would never run)")
    opt_fn = {"adamw": optim.adamw, "adafactor": optim.adafactor,
              "adamw8bit": optim.adamw_8bit}[args.optimizer]
    lr = args.lr if args.lr is not None else \
        (None if args.optimizer == "adafactor" else 3e-4)
    if args.warmup_steps > 0:
        if lr is None:
            raise ValueError(
                "--warmup-steps with adafactor needs an explicit --lr "
                "(the schedule drives an absolute step size, replacing "
                "adafactor's relative-step mode)")
        optimizer = optim.with_schedule(
            opt_fn,
            optim.warmup_cosine(lr, args.warmup_steps, args.steps))
    else:
        optimizer = opt_fn(lr)
    if args.clip_norm > 0:
        optimizer = optim.with_clipping(optimizer, args.clip_norm)
    if args.master_f32:
        # master wraps OUTSIDE the schedule (with_schedule rejects the
        # reverse composition)
        optimizer = optim.with_master_f32(optimizer)
    opt_state = optimizer.init(params)

    # ---- checkpoint/resume (utils/checkpoint.py): restore on the host
    # BEFORE device placement so the same code path serves both layouts
    start_step = 0
    ckpt_mgr = None
    if args.save:
        from distributed_pytorch_tpu.utils.checkpoint import (
            CheckpointManager, restore_checkpoint)
        if args.sharded_ckpt:
            # checkpoints follow the sharding: the same spec tree that
            # would drive the ZeRO layout decomposes the state into
            # owned shards, and a restore reshards onto whatever world
            # size the relaunch has (ckpt/, docs/checkpointing.md)
            from distributed_pytorch_tpu.parallel import shard_layouts
            p_specs, _, ax = shard_layouts(
                params, None, n_shards=max(world_size, 1))
            ckpt_mgr = CheckpointManager(
                args.save, interval=args.save_every, keep=3,
                async_save=True, sharded=True, param_specs=p_specs,
                axis_sizes=ax)
        else:
            ckpt_mgr = CheckpointManager(args.save,
                                         interval=args.save_every,
                                         keep=3, async_save=True)
        if args.resume:
            ck = restore_checkpoint(args.save, like_params=params,
                                    like_opt_state=opt_state)
            params, opt_state = ck.params, ck.opt_state
            start_step = ck.step + 1
            if not quiet:
                dist.print_primary(f"resumed from step {ck.step} "
                                   f"({args.save})")
    elif args.resume:
        raise ValueError("--resume requires --save DIR")

    if args.fused_ce:
        from distributed_pytorch_tpu.ops.losses import \
            fused_linear_cross_entropy

        def loss_fn(p, batch):
            x, y = batch
            hid = model.apply(p, x, return_hidden=True)
            loss = fused_linear_cross_entropy(hid, model.head_weight(p), y)
            # per-example nll is unavailable by design (the full logits
            # never exist); report the batch mean per example instead
            return loss, {"nll": jnp.broadcast_to(loss, (x.shape[0],))}
    else:
        def loss_fn(p, batch):
            x, y = batch
            per_ex = cross_entropy_per_example(model.apply(p, x), y)
            return per_ex.mean(), {"nll": per_ex}

    world = max(world_size, 1)
    if args.fsdp and is_distributed:
        mesh = context.get_mesh()
        specs = fsdp_param_specs(params, world)
        params, opt_state = shard_model_and_opt(params, opt_state, mesh,
                                                specs)
        step_fn = make_fsdp_train_step(loss_fn, optimizer, mesh, specs)
        place = lambda b: shard_batch_spec(b, mesh, P("dp", None))
    else:
        params = dist.replicate(params)
        opt_state = dist.replicate(opt_state)
        step_fn = make_train_step(loss_fn, optimizer)
        place = dist.shard_batch

    logger = MetricsLogger(args.log)
    tokens_per_step = world * args.batch_size * args.seq_len

    # The loop syncs with the device only every --log-every steps: a
    # host read (``float(loss)``) costs a full round trip, so the steps
    # in between stay async and pipeline back-to-back on the chip. The
    # per-step losses are still all recorded — as device scalars,
    # materialized in one batch at each boundary.
    pending = []   # (step, device loss) since the last sync

    def sync_pending():
        for s, dev_loss in pending:
            loss = float(np.asarray(dev_loss).mean())
            if history is not None:
                history.append(loss)
            logger.log(s, loss=loss)
        last = float(np.asarray(pending[-1][1]).mean()) if pending else None
        pending.clear()
        return last

    step = start_step
    # resume lands mid-epoch: restart that epoch's (set_epoch-seeded,
    # deterministic) stream from the right batch index — skipping happens
    # at the index level (loader.iter_from), so fast-forward is free
    epoch = step // len(loader)
    skip = step % len(loader)
    last_saved = None
    t_run0 = None
    timed_steps = 0
    trace_active = False
    while step < args.steps:
        loader.set_epoch(epoch)
        # one placement seam: batches leave the iterator device-resident
        # either way, so the step call is uniform
        if args.prefetch > 0:
            it = device_prefetch(loader.iter_from(skip),
                                 size=args.prefetch, place=place)
        else:
            it = map(place, loader.iter_from(skip))
        try:
            for batch in it:
                if step >= args.steps:
                    break
                if args.trace and step == min(5, args.steps - 1):
                    profiler.start_trace(args.trace)
                    trace_active = True
                out = step_fn(params, opt_state, batch)
                params, opt_state = out[0], out[1]
                pending.append((step, out.loss))
                if trace_active and (step >= 10 or step == args.steps - 1):
                    jax.block_until_ready(out.loss)
                    profiler.stop_trace()
                    trace_active = False
                if step % args.log_every == 0 or step == args.steps - 1:
                    loss = sync_pending()
                    if t_run0 is None and step >= 1:
                        t_run0 = (time.perf_counter(), step)  # past compile
                    if not quiet:
                        dist.print_primary(
                            f"step {step:>5}  loss {loss:.4f}")
                if ckpt_mgr is not None and \
                        ckpt_mgr.save(step, params, opt_state,
                                      extra={"epoch": epoch}):
                    last_saved = step
                step += 1
        finally:
            # breaking at --steps must stop the prefetch worker and free
            # its queued device batches before eval/generate allocate
            if hasattr(it, "close"):
                it.close()
        epoch += 1
        skip = 0
    sync_pending()
    jax.block_until_ready(params)
    if ckpt_mgr is not None:
        if step > start_step and last_saved != step - 1:
            ckpt_mgr.save(step - 1, params, opt_state,
                          extra={"epoch": (step - 1) // len(loader)},
                          force=True)
        ckpt_mgr.wait()

    if t_run0 is not None and step - t_run0[1] > 0 and not quiet:
        dt = time.perf_counter() - t_run0[0]
        timed_steps = step - t_run0[1]
        sps = timed_steps / dt
        dist.print_primary(
            f"done: {sps:.2f} steps/s, {sps * tokens_per_step:,.0f} "
            f"tokens/s (mean step {1e3 / sps:.2f} ms, "
            f"{timed_steps} timed steps)")

    if eval_set is not None:
        from distributed_pytorch_tpu.parallel import make_eval_step

        eval_sampler = dist.data_sampler(eval_set, is_distributed,
                                         shuffle=False)
        eval_loader = DataLoader(eval_set, batch_size=args.batch_size,
                                 sampler=eval_sampler, drop_last=True)
        if len(eval_loader) == 0:
            dist.print_primary("eval: holdout smaller than one global "
                               "batch; skipping")
        else:
            if args.fused_ce:
                # eval must not materialize the full logits either — a
                # batch that only fits in HBM because of --fused-ce would
                # OOM here after the whole training run. Broadcasting the
                # local-batch mean to per-example shape keeps the
                # make_eval_step contract; with drop_last all shards are
                # equal-sized, so the mean of means is the exact mean.
                def eval_fn(p, batch):
                    x, y = batch
                    hid = model.apply(p, x, return_hidden=True)
                    loss = fused_linear_cross_entropy(hid, model.head_weight(p), y)
                    return jnp.broadcast_to(loss, (x.shape[0],))
            else:
                def eval_fn(p, batch):
                    x, y = batch
                    return cross_entropy_per_example(model.apply(p, x), y)

            # FSDP-sharded params work unchanged (eval_fn is replicated
            # code; the partitioner gathers as needed)
            ev = (make_eval_step(eval_fn) if not (args.fsdp and
                                                  is_distributed)
                  else jax.jit(eval_fn))
            nlls = [np.asarray(ev(params, place(b))).reshape(-1)
                    for b in eval_loader]
            nll = float(np.concatenate(nlls).mean())
            logger.log(step, eval_nll=nll)
            if not quiet:
                dist.print_primary(
                    f"eval: nll {nll:.4f}  ppl {np.exp(min(nll, 20)):.2f}")

    if args.generate > 0:
        from distributed_pytorch_tpu.models import make_generate_fn
        # generation runs on replicated single-program params
        gen_params = jax.device_get(params)
        x0, _ = dataset[0]
        p_len = max(1, min(16, args.seq_len, model.max_seq - args.generate))
        prompt = jnp.asarray(np.asarray(x0)[:p_len][None], jnp.int32)
        gen = jax.jit(make_generate_fn(model, args.generate))
        toks = np.asarray(gen(gen_params, prompt,
                              jax.random.PRNGKey(0)))[0]
        if args.text:
            dist.print_primary("generated:",
                               bytes(toks.tolist()).decode(errors="replace"))
        else:
            dist.print_primary("generated tokens:", toks.tolist())

    logger.close()
    dist.cleanup()
    return params


def _elastic_entry():
    """Spawn-side entrypoint for ``--elastic``: run the normal worker,
    resuming automatically whenever the save dir already holds a
    checkpoint (the relaunch after a crash must not restart from
    step 0 — and must not require the user to have typed --resume)."""
    import sys as _sys

    from distributed_pytorch_tpu.utils.checkpoint import latest_step

    argv = list(_sys.argv[1:])
    args = parse_args(argv)
    if args.save and latest_step(args.save) is not None \
            and "--resume" not in argv:
        argv.append("--resume")
    dist.launch(main_worker, argv)


if __name__ == "__main__":
    _args = parse_args()
    if _args.elastic:
        if not _args.save:
            raise SystemExit("--elastic requires --save DIR")
        from distributed_pytorch_tpu.runtime import elastic
        res = elastic.elastic_run(_elastic_entry,
                                  max_restarts=_args.elastic)
        if res.restarts:
            print(f"finished after {res.restarts} relaunch(es)")
    else:
        dist.launch(main_worker)
