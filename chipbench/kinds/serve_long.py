"""A served model whose requests are LONG (prompts up to 32 k tokens)
under the open loop of ``kinds/serve.py``: that module's ``run`` whole
(the schedule, clocks, window, lead-in, drain, traced part, the sample, the
choice of the requests checked and the check's limit), with the two things
it cannot do for such a cell put in its place for the run:

- ``build``: the MODEL is built before a single weight, without the flash
  ``attn_fn`` (a layer that was told its window computes it itself), so
  that a program without the mechanism (the parent of the PR that brought
  ``layer_windows``) fails in seconds on an unknown keyword, before 7 GB
  of weights;
- the reference's walk: ``reference/serve_logits_rows.py``, one request at
  a time. ``reference/serve_logits.py`` stands the checked requests side
  by side, padded to the mix's longest: 8 x 33 280 x 6144 float32 values
  going into a layer and as many coming out are 13 GB.

``run`` takes ``broken=`` and ``control_mm=`` as that module's does."""

import importlib
from unittest import mock

import jax.numpy as jnp

from chipbench import weights as W
from chipbench.kinds import serve
from chipbench.reference import serve_logits_rows


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
    params = adapter.to_program(W.make(seed, cfg, jnp.bfloat16))
    return InferenceEngine(model, params, EngineConfig(**e))


def run(cell, devices, tracer, t_start, broken=None, control_mm=None):
    with mock.patch.object(serve, "build", build), \
            mock.patch.object(serve, "serve_logits", serve_logits_rows):
        return serve.run(cell, devices, tracer, t_start, broken=broken,
                         control_mm=control_mm)
