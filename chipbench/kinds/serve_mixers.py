"""A served model of linear- and sparse-attention layers (MiniCPM-SALA)
under the open loop of ``kinds/serve.py``, at long contexts: that module's
``run`` whole (the schedule, clocks, window, lead-in, drain, traced part,
the sample, the choice of the requests checked and the check's limit),
with the two things it cannot do for such a cell put in its place for the
run, as ``kinds/serve_long.py`` does for its family:

- ``build``: the MODEL is built before a single weight and without a flash
  ``attn_fn`` (its layers mix by their own rule), so that a program
  without ``layer_mixers`` (the parent of the PR that brought it) fails in
  seconds on the unknown keyword, before 5.6 GB of weights; the adapter
  takes the configuration (the q/k norms' gains);
- the reference's walk: ``reference/serve_logits_mixers.py``, one request
  at a time, a layer kind a program;
- two more numbers that ``correct`` compares, of a linear layer's STATE,
  which no logit shows closely (a state rounded to bfloat16 every step is
  a percent off in its slowest head and moves a served token's logit by
  less than the activations' own rounding does: PERF.md Findings, PR 45).
  After the warm-up, in the set-up, ONE request of the mix's
  ``state_probe`` (a prompt under ``dense_len`` and an answer of the
  warmed lengths, tokens from the seed) is served alone through the same
  engine, and every linear layer's state of its slot, the
  slowest-decaying head's, is read from the pool:
  ``served_state_gap_max``, its largest relative distance (Frobenius)
  over the layers from the reference's state after the same tokens (a
  state that was not zeroed, a wrong decay, a lost step), and
  ``served_state_bfloat16_share``, the largest share of its values that
  bfloat16 holds exactly (a float32 sum's low bits are zero one time in
  65 536; a state kept in bfloat16 reads 1).

``run`` takes ``broken=`` and ``control_mm=`` as that module's does.

The two seeded faults of such a cell (``chipbench/faults_mixers.py`` reads
them on the chip, the CPU tests at a tiny size), each a patch of the
program for the length of a run: :func:`fault_dense_attention` and
:func:`fault_bfloat16_state`. A comparison that cannot tell selection
from no selection, or a float32 state from a rounded one, guards
neither."""

import contextlib
import importlib
from unittest import mock

import jax.numpy as jnp
import numpy as np

from chipbench import weights as W
from chipbench.kinds import serve
from chipbench.reference import serve_logits_mixers


def build(cell, seed):
    from distributed_pytorch_tpu import models
    from distributed_pytorch_tpu.serve import EngineConfig, InferenceEngine

    cfg, mix = cell.config, cell.traffic
    e = dict(mix["engine"])
    e["buckets"] = tuple(e["buckets"])
    adapter = importlib.import_module(f"chipbench.adapters.{cfg['family']}")
    model = models.TransformerLM(
        **adapter.model_kwargs(cfg, max_len=e["max_len"]),
        dtype=jnp.bfloat16)
    params = adapter.to_program(W.make(seed, cfg, jnp.bfloat16), cfg)
    return InferenceEngine(model, params, EngineConfig(**e))


def state_probe(eng, cell):
    """Serve the mix's ``state_probe`` request alone (the engine is idle:
    the warm-up has finished) and read what it left: ``(prompt, tokens,
    [(n_slots, d, d) a linear layer])``, the slowest head's state of
    every slot (the request's own is the one that agrees with the
    reference; a slot is not told apart by asking the engine)."""
    from distributed_pytorch_tpu.serve import SamplingParams

    mix = cell.traffic
    rng = np.random.default_rng([cell.seed, 5])
    prompt = rng.integers(0, cell.config["vocab_size"],
                          mix["state_probe"]["prompt_tokens"]).astype(np.int32)
    tokens = eng.submit(prompt, SamplingParams(
        max_new_tokens=mix["state_probe"]["answer_tokens"])).result(
            timeout=1200)
    pool = eng.pool
    return prompt, np.asarray(tokens, np.int32), [
        np.asarray(pool.state[i].s[:, -1]) for i in pool.state_layers]


def state_gaps(served, reference):
    """A layer's relative distance (Frobenius) of the slot that agrees
    best with the reference's slowest head, and the share of that slot's
    values that bfloat16 holds exactly: ``[(gap, share) a layer]``."""
    out = []
    for slots, ref in zip(served, reference):
        ref = ref[-1]                              # the slowest head's
        off = np.sqrt(np.sum(np.square(slots - ref[None]), axis=(1, 2)))
        own = slots[int(off.argmin())]
        kept = np.asarray(jnp.asarray(own).astype(jnp.bfloat16)
                          .astype(jnp.float32))
        out.append((float(off.min() / np.sqrt(np.sum(ref * ref))),
                    float(np.mean(kept == own))))
    return out


def state_gap(served, reference):
    """The largest of the layers' distances."""
    return max(gap for gap, _ in state_gaps(served, reference))


def run(cell, devices, tracer, t_start, broken=None, control_mm=None):
    probe, reference = [], serve_logits_mixers.Reference()
    warm_up = serve.warm_up

    def warm_up_and_probe(eng, mix, vocab):
        warm_up(eng, mix, vocab)
        probe.extend(state_probe(eng, cell))

    with mock.patch.object(serve, "build", build), \
            mock.patch.object(serve, "serve_logits", reference), \
            mock.patch.object(serve, "warm_up", warm_up_and_probe):
        out = serve.run(cell, devices, tracer, t_start, broken=broken,
                        control_mm=control_mm)
    prompt, tokens, served = probe
    mix = cell.traffic
    ref = reference.served_states(
        cell.config, cell.seed, prompt, tokens, jnp.bfloat16,
        width=mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"],
        control_mm=control_mm)
    if control_mm is not None:
        print(f"chipbench: control served_state_gap_max "
              f"{state_gap([c[-1:] for c in ref['control']], ref['reference']):.6g}",
              flush=True)
    cell.phases.end("reference_state")
    per_layer = state_gaps(served, ref["reference"])
    print("chipbench: the probe's states, a linear layer: distance from the "
          "reference " + " ".join(f"{g:.5f}" for g, _ in per_layer)
          + "; share of values bfloat16 holds "
          + " ".join(f"{b:.5f}" for _, b in per_layer), flush=True)
    for name, value in (
            ("served_state_gap_max", max(g for g, _ in per_layer)),
            ("served_state_bfloat16_share", max(b for _, b in per_layer))):
        out["checks"].append({"name": name, "value": value,
                              "limit": cell.limits[name]})
    return out


def fault_dense_attention():
    """A program that attends densely where it should select: every
    prompt is admitted as one shorter than ``dense_len``, so every row
    reads all of its earlier positions, in prefill and in decode."""
    from distributed_pytorch_tpu.serve.pages.cache import PagedSlotPool
    real = PagedSlotPool.chunk

    def chunk(self, params, slot):
        self.dense_len = 1 << 30
        return real(self, params, slot)

    return mock.patch.object(PagedSlotPool, "chunk", chunk)


@contextlib.contextmanager
def fault_bfloat16_state():
    """A program that keeps a linear layer's state in bfloat16: what a
    decode step and a prompt's chunk leave in the store is rounded to
    bfloat16's 8 exponent and 7 mantissa bits, as an array of that type
    would hold it (``reduce_precision``: a cast there and back is one the
    TPU's compiler may drop as excess precision, and did)."""
    import jax
    from distributed_pytorch_tpu.nn.paged import StatePages
    step, write = StatePages.step, StatePages.write
    rounded = lambda st: StatePages(jax.lax.reduce_precision(st.s, 8, 7))
    with mock.patch.object(
            StatePages, "step", lambda self, *a: rounded(step(self, *a))), \
        mock.patch.object(
            StatePages, "write", lambda self, *a: rounded(write(self, *a))):
        yield


FAULTS = {"dense_attention": fault_dense_attention,
          "bfloat16_state": fault_bfloat16_state}
