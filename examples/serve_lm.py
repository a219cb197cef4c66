"""Serve an LM with continuous batching — the serving front door, live.

Builds a small ``TransformerLM``, starts the ``serve.InferenceEngine``,
submits a handful of concurrent requests with mixed prompts / sampling
configs / priorities, STREAMS tokens to stdout as they are produced
(per-token callbacks), then prints each request's SLO record and the
engine's compile/occupancy stats. Runs on CPU in seconds:

    python examples/serve_lm.py [--requests N] [--max-new N]
        [--slots N] [--temperature T] [--metrics-log FILE]
        [--shared-prefix N] [--disagg] [--handoff-width W]

With --metrics-log, per-request TTFT/TPOT events and periodic engine
records are appended as line-JSON (the same stream training metrics
use — utils/logging.MetricsLogger). The engine runs the paged,
prefix-shared KV cache (serve/pages/); --shared-prefix N gives
every request the same N-token "system prompt", so the printed
per-request records show the prefix pages being computed once and hit
thereafter (prefix_hit_pages / prefill_tokens_saved). With --disagg
(default from DPX_SERVE_DISAGG) the requests run through the
DISAGGREGATED split (serve/disagg/): separate prefill and decode
engines joined by the KV-page handoff, --handoff-width f32|q8|q4
choosing the frame wire — the per-request lines then print the TTFT
decomposition (queue/prefill/handoff/decode) and handoff bytes live.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from distributed_pytorch_tpu import models  # noqa: E402
from distributed_pytorch_tpu.serve import (EngineConfig,  # noqa: E402
                                           InferenceEngine, SamplingParams)
from distributed_pytorch_tpu.utils.logging import MetricsLogger  # noqa: E402


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="continuous-batching LM serving")
    p.add_argument("--requests", type=int, default=6)
    p.add_argument("--max-new", type=int, default=24)
    p.add_argument("--slots", type=int, default=3)
    p.add_argument("--max-len", type=int, default=128)
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--metrics-log", type=str, default=None)
    p.add_argument("--shared-prefix", type=int, default=0,
                   help="give every request the same N-token system "
                        "prompt (shows prefix sharing)")
    from distributed_pytorch_tpu.runtime import env as dpxenv
    p.add_argument("--disagg", action="store_true",
                   default=bool(dpxenv.get("DPX_SERVE_DISAGG")),
                   help="disaggregated prefill/decode split "
                        "(serve/disagg/; default DPX_SERVE_DISAGG)")
    p.add_argument("--handoff-width", type=str, default=None,
                   choices=("f32", "q8", "q4"),
                   help="wire width of the KV-page handoff frame "
                        "(default DPX_HANDOFF_WIDTH)")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    model = models.TransformerLM(vocab=61, dim=64, n_layers=2, n_heads=4,
                                 n_kv_heads=2, pos="rope", max_seq=256)
    params = model.init(jax.random.PRNGKey(0))
    logger = MetricsLogger(path=args.metrics_log) if args.metrics_log \
        else None
    if args.disagg:
        from distributed_pytorch_tpu.serve import (DisaggConfig,
                                                   DisaggEngine)
        cfg = DisaggConfig(n_slots=args.slots, max_len=args.max_len,
                           metrics=logger, log_every=8,
                           handoff_width=args.handoff_width)
        make_engine = lambda: DisaggEngine(model, params, cfg)  # noqa: E731
    else:
        cfg = EngineConfig(n_slots=args.slots, max_len=args.max_len,
                           metrics=logger, log_every=8)
        make_engine = lambda: InferenceEngine(model, params, cfg)  # noqa: E731
    rng = np.random.default_rng(0)
    shared = rng.integers(0, 61, (args.shared_prefix,)).astype(np.int32) \
        if args.shared_prefix else None

    def stream(rid):
        def cb(tok, i):
            print(f"  [req {rid}] token {i}: {tok}", flush=True)
        return cb

    with make_engine() as eng:
        handles = []
        for i in range(args.requests):
            prompt = rng.integers(0, 61,
                                  (int(rng.integers(4, 20)),)).astype(
                np.int32)
            if shared is not None:
                prompt = np.concatenate([shared, prompt])
            sp = SamplingParams(
                max_new_tokens=args.max_new,
                # mix greedy and sampled requests (distinct sampler
                # configs each compile once — engine stats show it)
                temperature=0.0 if i % 2 == 0 else args.temperature,
                top_k=None if i % 2 == 0 else 8,
                priority=0 if i == args.requests - 1 else 5,
            )
            h = eng.submit(prompt, sp, rng=jax.random.PRNGKey(i),
                           on_token=stream(i))
            handles.append(h)
            print(f"submitted req {h.request_id}: prompt_len "
                  f"{len(prompt)}, max_new {sp.max_new_tokens}, "
                  f"T={sp.temperature}, priority {sp.priority}")
        for h in handles:
            toks = h.result(timeout=300)
            m = h.metrics
            line = (f"req {h.request_id} done: {len(toks)} tokens, "
                    f"TTFT {m['ttft_ms']:.1f} ms")
            if m["tpot_ms"]:
                line += f", TPOT {m['tpot_ms']:.2f} ms"
            if args.disagg:
                line += (f" [queue {m['queue_ms']:.0f} + prefill "
                         f"{m['prefill_ms']:.0f} + handoff "
                         f"{m['handoff_ms']:.1f} + decode "
                         f"{m['decode_ms']:.0f} ms; "
                         f"{m['handoff_bytes']} handoff B, "
                         f"prefix hit {m['prefix_hit_pages']} pages]")
            else:
                line += (f", prefix hit {m['prefix_hit_pages']} pages "
                         f"({m['prefill_tokens_saved']} prefill tokens "
                         f"saved)")
            print(line)
        st = eng.stats()
        if args.disagg:
            print(f"split: decode compiles "
                  f"{st['decode']['decode_compiles']} (prefill-side "
                  f"{st['prefill']['decode_compiles']}), prefill "
                  f"compiles {st['prefill']['prefill_compiles']}, "
                  f"{st['handoff']['frames_sent']} frames / "
                  f"{st['handoff']['bytes_sent']} handoff bytes "
                  f"({st['handoff_width']})")
        else:
            print(f"engine: {st['iterations']} iterations, "
                  f"{st['tokens_emitted']} tokens, decode compiles "
                  f"{st['decode_compiles']}, prefill compiles "
                  f"{st['prefill_compiles']}, samplers "
                  f"{st['sample_compiles']}")
        if not args.disagg:
            ps = st["pages"]
            hr = ps["prefix_hit_rate"]
            print(f"pages: {ps['pages_in_use']}/{ps['n_pages']} in use "
                  f"(page_len {ps['page_len']}), hit rate "
                  f"{hr if hr is None else round(hr, 3)}, "
                  f"{ps['evictions']} evictions")
    if logger is not None:
        logger.close()
        print(f"metrics -> {args.metrics_log}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
