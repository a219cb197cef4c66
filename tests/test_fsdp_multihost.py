"""FSDP (ZeRO-3 layout) numerics + sharding, and multi-host helpers on the
8-device virtual mesh. FSDP must be a pure layout change: identical loss
trajectory to replicated DP, with params/grads/moments actually sharded."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from distributed_pytorch_tpu import models, optim
from distributed_pytorch_tpu.ops.losses import cross_entropy_per_example
from distributed_pytorch_tpu.parallel import (fsdp_param_specs,
                                              make_fsdp_train_step,
                                              make_spmd_train_step,
                                              shard_batch_spec,
                                              shard_model_and_opt)
from distributed_pytorch_tpu.parallel.fsdp import opt_state_specs
from distributed_pytorch_tpu.parallel.tensor import shard_params
from distributed_pytorch_tpu.runtime import context, multihost


def _mesh8():
    return context.init_mesh(dp=8)


def _lm():
    # dims chosen divisible by 8 so every big leaf shards
    return models.TransformerLM(vocab=64, dim=32, n_layers=2, n_heads=4,
                                max_seq=16)


def _loss_fn(model):
    def loss_fn(p, batch):
        x, y = batch
        return cross_entropy_per_example(model.apply(p, x), y).mean(), {}
    return loss_fn


class TestFsdpSpecs:
    def test_largest_divisible_dim_sharded(self):
        params = {"w": jnp.zeros((48, 64)), "b": jnp.zeros((7,)),
                  "tiny": jnp.zeros((8, 8))}
        specs = fsdp_param_specs(params, 8, min_size=128)
        assert specs["w"] == P(None, "dp")      # 64 is the largest dim % 8
        assert specs["b"] == P()                # 7 not divisible
        assert specs["tiny"] == P()             # below min_size


    def test_base_specs_respected(self):
        params = {"w": jnp.zeros((64, 128))}
        base = {"w": P(None, "tp")}             # tp already owns dim 1
        specs = fsdp_param_specs(params, 8, min_size=1, base_specs=base)
        assert specs["w"] == P("dp", "tp")      # fsdp takes the free dim

    def test_opt_state_specs_adamw(self):
        params = {"w": jnp.zeros((64, 64))}
        p_specs = fsdp_param_specs(params, 8, min_size=1)
        state = optim.adamw(1e-3).init(params)
        o = opt_state_specs(state, p_specs)
        assert o.step == P()
        assert o.mu["w"] == p_specs["w"] and o.nu["w"] == p_specs["w"]


class TestFsdpNumerics:
    @pytest.mark.slow
    def test_matches_replicated_dp(self):
        """ZeRO-3 is a layout, not math: the loss trajectory must equal
        replicated data parallelism step for step."""
        mesh = _mesh8()
        model = _lm()
        loss_fn = _loss_fn(model)
        opt = optim.adamw(1e-3)
        rng = np.random.default_rng(0)
        toks = rng.integers(0, 64, (16, 16)).astype(np.int32)
        batch = shard_batch_spec((toks, toks), mesh, P("dp", None))

        # replicated baseline
        from distributed_pytorch_tpu.parallel import replicated_specs
        p0 = model.init(jax.random.PRNGKey(0))
        p_rep = shard_params(p0, replicated_specs(p0), mesh)
        o_rep = opt.init(p_rep)
        step_rep = make_spmd_train_step(loss_fn, opt, donate=False)

        # fsdp
        params = model.init(jax.random.PRNGKey(0))
        specs = fsdp_param_specs(params, 8, min_size=1)
        opt_state = opt.init(params)
        params, opt_state = shard_model_and_opt(params, opt_state, mesh,
                                                specs)
        step_fsdp = make_fsdp_train_step(loss_fn, opt, mesh, specs,
                                         donate=False)

        for _ in range(3):
            out_r = step_rep(p_rep, o_rep, batch)
            out_f = step_fsdp(params, opt_state, batch)
            p_rep, o_rep = out_r.params, out_r.opt_state
            params, opt_state = out_f.params, out_f.opt_state
            np.testing.assert_allclose(float(out_f.loss), float(out_r.loss),
                                       rtol=1e-5)

    @pytest.mark.slow
    @pytest.mark.parametrize("stage", ["zero1", "zero2"])
    def test_zero_stages_match_replicated_dp_and_shard_state(self, stage):
        """ZeRO-1 (replicated grads) and ZeRO-2 (reduce-scattered grads):
        replicated params + sharded optimizer state are pure layout —
        loss trajectory equals replicated DP; after a step the params
        stay whole per device while the AdamW moments hold 1/8 shards.
        The two rungs differ only in gradient layout (internal to the
        compiled step), so both pin against the same oracle."""
        from distributed_pytorch_tpu.parallel import (make_zero1_train_step,
                                                      make_zero2_train_step,
                                                      replicated_specs)
        from distributed_pytorch_tpu.parallel.fsdp import opt_state_specs
        make_step = {"zero1": make_zero1_train_step,
                     "zero2": make_zero2_train_step}[stage]

        mesh = _mesh8()
        model = _lm()
        loss_fn = _loss_fn(model)
        opt = optim.adamw(1e-3)
        rng = np.random.default_rng(0)
        toks = rng.integers(0, 64, (16, 16)).astype(np.int32)
        batch = shard_batch_spec((toks, toks), mesh, P("dp", None))

        p0 = model.init(jax.random.PRNGKey(0))
        p_rep = shard_params(p0, replicated_specs(p0), mesh)
        o_rep = opt.init(p_rep)
        step_rep = make_spmd_train_step(loss_fn, opt, donate=False)

        params = shard_params(model.init(jax.random.PRNGKey(0)),
                              replicated_specs(p0), mesh)
        step_z, s_specs = make_step(loss_fn, opt, mesh, params,
                                    min_size=1, donate=False)
        o_raw = opt.init(params)
        opt_state = shard_params(
            o_raw, opt_state_specs(o_raw, s_specs, params=params), mesh)

        for _ in range(3):
            out_r = step_rep(p_rep, o_rep, batch)
            out_z = step_z(params, opt_state, batch)
            p_rep, o_rep = out_r.params, out_r.opt_state
            params, opt_state = out_z.params, out_z.opt_state
            np.testing.assert_allclose(float(out_z.loss),
                                       float(out_r.loss), rtol=1e-5)

        w = params["blocks"][0]["fc1"]["w"]
        assert w.addressable_shards[0].data.size == w.size  # replicated
        mu = opt_state.mu["blocks"][0]["fc1"]["w"]
        assert mu.addressable_shards[0].data.size == mu.size // 8

    def test_state_actually_sharded(self):
        mesh = _mesh8()
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        specs = fsdp_param_specs(params, 8, min_size=1)
        opt = optim.adamw(1e-3)
        params, opt_state = shard_model_and_opt(params, opt.init(params),
                                                mesh, specs)
        w = params["blocks"][0]["fc1"]["w"]
        assert "dp" in jax.tree_util.tree_leaves(
            [w.sharding.spec])[0] or "dp" in tuple(w.sharding.spec)
        # local shard is 1/8 of the global array
        shard = w.addressable_shards[0].data
        assert shard.size == w.size // 8
        mu = opt_state.mu["blocks"][0]["fc1"]["w"]
        assert mu.addressable_shards[0].data.size == mu.size // 8

        # updated state keeps the sharded layout (no silent re-replication)
        loss_fn = _loss_fn(model)
        rng = np.random.default_rng(1)
        toks = rng.integers(0, 64, (16, 16)).astype(np.int32)
        batch = shard_batch_spec((toks, toks), mesh, P("dp", None))
        out = make_fsdp_train_step(loss_fn, opt, mesh, specs,
                                   donate=False)(params, opt_state, batch)
        w2 = out.params["blocks"][0]["fc1"]["w"]
        assert w2.addressable_shards[0].data.size == w2.size // 8


class TestMultihost:
    def test_single_host_degradation(self):
        multihost.initialize()  # no-op off-pod
        assert multihost.num_hosts() == 1
        assert multihost.host_index() == 0
        assert multihost.is_primary_host()
        start, stop = multihost.local_device_slice()
        assert (start, stop) == (0, len(jax.local_devices()))

    def test_hybrid_mesh_single_host(self):
        mesh = multihost.init_hybrid_mesh(ici=[("dp", 4), ("tp", 2)])
        assert mesh.shape == {"dp": 4, "tp": 2}
        mesh2 = multihost.init_hybrid_mesh(ici=[("dp", 8)],
                                           dcn=[("dp_outer", 1)])
        assert mesh2.shape == {"dp_outer": 1, "dp": 8}

    def test_hybrid_mesh_size_mismatch_raises(self):
        with pytest.raises(ValueError, match="devices"):
            multihost.init_hybrid_mesh(ici=[("dp", 4)])

    def test_hybrid_mesh_usable_for_compute(self):
        mesh = multihost.init_hybrid_mesh(ici=[("dp", 8)])
        x = jnp.arange(16.0)
        y = jax.jit(
            lambda x: x * 2,
            in_shardings=jax.NamedSharding(mesh, P("dp")),
            out_shardings=jax.NamedSharding(mesh, P("dp")))(x)
        np.testing.assert_allclose(np.asarray(y), np.arange(16.0) * 2)

    def test_control_plane_helpers(self):
        g = multihost.process_allgather(np.array([1.5, 2.5]))
        assert g.shape == (1, 2)
        b = multihost.broadcast_from_primary(np.array([3]))
        np.testing.assert_array_equal(b, [3])


@functools.lru_cache(maxsize=1)
def _dcn_capability():
    """Probe whether THIS environment can form real cross-process DCN
    device visibility (two jax.distributed processes whose jax.devices()
    span both hosts). Some CI/dev containers rendezvous fine but never
    merge device views — the full test would fail on an environment
    limitation, not a code bug, so the tier-1 gate skips with the
    probe's reason instead (ISSUE 5 satellite). Returns a tri-state
    verdict: ``capable`` / ``incapable`` (the worker's deliberate exit
    31) / ``broken`` (any other crash — the gate FAILS on those rather
    than hiding a real regression behind a skip). Cached per session:
    the probe costs two jax startups."""
    import os
    import subprocess
    import sys as _sys

    from distributed_pytorch_tpu.runtime.launcher import find_free_port

    # _multihost_worker.PROBE_INCAPABLE — referenced by value: importing
    # the worker module would run its platform and device-count switch
    # inside THIS test process
    PROBE_INCAPABLE = 31

    here = os.path.dirname(os.path.abspath(__file__))
    worker = os.path.join(here, "_multihost_worker.py")
    coord = f"127.0.0.1:{find_free_port()}"
    procs = [subprocess.Popen(
        [_sys.executable, worker, "--probe", coord, "2", str(i)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for i in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=120)
            outs.append(out.strip())
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        # a hung probe is NOT the worker's deliberate incapable verdict:
        # localhost rendezvous answers in seconds when healthy, so a
        # deadlock here is a regression signal and must fail, not skip
        return "broken", ("DCN probe hung (jax.distributed rendezvous "
                          "deadlocked past 120s)")
    codes = [p.returncode for p in procs]
    if all(rc == 0 for rc in codes):
        return "capable", ""
    if all(rc in (0, PROBE_INCAPABLE) for rc in codes):
        # the worker's deliberate verdict, not a crash: skippable
        return "incapable", ("real cross-process DCN unavailable in this "
                             "environment: " + "; ".join(outs))
    # any OTHER exit means the probe itself broke (an import error, a
    # regression in multihost.initialize) — that must FAIL tier-1, not
    # silently skip it
    return "broken", (f"DCN probe crashed (exit codes {codes}): "
                      + "; ".join(outs))


class TestRealMultiProcess:
    def test_two_process_dcn_step(self):
        """REAL multi-process jax.distributed: two OS processes with a
        local coordinator, 4 CPU devices each -> 8 global devices;
        asserts process_count()==2 and runs a gradient-averaging DP step
        whose collective crosses the process boundary, plus the
        control-plane allgather/broadcast helpers. (The reference cannot
        do any of this: its rendezvous is hardcoded localhost-single-node,
        reference distributed.py:48.) Workers run tests/_multihost_worker.py
        in fresh subprocesses — platform selection must precede backend
        init, so this cannot run in-process. Gated on a capability probe:
        environments that cannot merge device views across processes
        SKIP with the probe's reason rather than failing tier-1."""
        import os
        import subprocess
        import sys as _sys

        from distributed_pytorch_tpu.runtime.launcher import find_free_port

        verdict, reason = _dcn_capability()
        if verdict == "broken":
            pytest.fail(reason)
        if verdict == "incapable":
            pytest.skip(reason)
        here = os.path.dirname(os.path.abspath(__file__))
        worker = os.path.join(here, "_multihost_worker.py")
        coord = f"127.0.0.1:{find_free_port()}"
        procs = [
            subprocess.Popen(
                [_sys.executable, worker, coord, "2", str(i)],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
            for i in range(2)
        ]
        outs = []
        try:
            for p in procs:
                out, _ = p.communicate(timeout=240)
                outs.append(out)
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
            pytest.fail(f"multi-process workers hung; partial: {outs}")
        assert all(p.returncode == 0 for p in procs), "\n".join(outs)
        assert any("proc 0 ok" in o for o in outs)
        assert any("proc 1 ok" in o for o in outs)


def test_fsdp_shards_master_f32_and_accum_states():
    """Composed optimizer wrappers (master-f32, accumulation) must keep
    their param-sized buffers FSDP-sharded, not silently replicated."""
    mesh = _mesh8()
    try:
        from distributed_pytorch_tpu.optim import (accumulate, adamw,
                                                   constant,
                                                   with_master_f32,
                                                   with_schedule)

        params = {"w": jnp.zeros((64, 64), jnp.bfloat16)}
        specs = fsdp_param_specs(params, 8, min_size=16)
        opt = accumulate(with_master_f32(adamw(1e-3)), 2)
        state = opt.init(params)
        s = opt_state_specs(state, specs)
        # acc buffer, master copy, and both moments all carry the param spec
        assert s.acc == specs
        assert s.inner.master == specs
        assert s.inner.inner.mu == specs and s.inner.inner.nu == specs
        assert s.count == P() and s.inner.inner.step == P()

        # scheduled optimizers shard their inner moments too
        opt2 = with_schedule(adamw, constant(1e-3))
        s2 = opt_state_specs(opt2.init(params), specs)
        assert s2.inner.mu == specs and s2.inner.nu == specs
        assert s2.step == P()
    finally:
        import distributed_pytorch_tpu as dist
        dist.cleanup()


def test_fsdp_fused_ce_matches_unfused(group8):
    """fused_linear_cross_entropy under FSDP: the head weight reaches the
    loss as a dp-sharded leaf; the chunked scan must produce the same
    loss as the materialized-logits path and train."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from distributed_pytorch_tpu import models, optim
    from distributed_pytorch_tpu.ops.losses import (
        cross_entropy, fused_linear_cross_entropy)
    from distributed_pytorch_tpu.parallel.fsdp import (
        fsdp_param_specs, make_fsdp_train_step, shard_model_and_opt)
    from distributed_pytorch_tpu.parallel.spmd import shard_batch_spec
    from distributed_pytorch_tpu.runtime import context
    from jax.sharding import PartitionSpec as P

    model = models.TransformerLM(vocab=64, dim=32, n_layers=2, n_heads=4,
                                 max_seq=16)
    params0 = model.init(jax.random.PRNGKey(0))
    opt = optim.adamw(1e-3)
    mesh = context.get_mesh()
    specs = fsdp_param_specs(params0, 8, min_size=64)
    params, opt_state = shard_model_and_opt(params0, opt.init(params0),
                                            mesh, specs)

    def loss_fused(p, batch):
        toks = batch
        hid = model.apply(p, toks[:, :-1], return_hidden=True)
        return fused_linear_cross_entropy(hid, p["head"]["w"],
                                          toks[:, 1:], chunk_rows=16), {}

    toks = np.random.default_rng(0).integers(0, 64, (8, 17)).astype(np.int32)
    # reference BEFORE the donating step consumes the shared buffers
    ref = float(cross_entropy(
        model.apply(params0, jnp.asarray(toks[:, :-1])),
        jnp.asarray(toks[:, 1:])))
    step = make_fsdp_train_step(loss_fused, opt, mesh, specs)
    batch = shard_batch_spec(toks, mesh, P("dp", None))
    out = step(params, opt_state, batch)
    np.testing.assert_allclose(float(out.loss), ref, rtol=2e-5)

    l0 = float(out.loss)
    for _ in range(3):
        out = step(out.params, out.opt_state, batch)
    assert float(out.loss) < l0


def test_opt_state_specs_adamw_8bit_codes_shard():
    """adamw_8bit's quantized moments shard under the FSDP layout: the
    param-shaped int8 code arrays inherit the param specs, per-block
    scales replicate — the '8-bit on top of ZeRO' composition is a real
    sharding, not a silent P() fallback."""
    params = {"w": jnp.zeros((64, 64), jnp.float32)}
    p_specs = fsdp_param_specs(params, 8, min_size=1)
    state = optim.adamw_8bit(1e-3).init(params)
    o = opt_state_specs(state, p_specs, params=params)
    assert o.step == P()
    assert o.mu["w"].q == p_specs["w"]
    assert o.nu["w"].q == p_specs["w"]
    assert o.mu["w"].scale == P()
    assert o.nu["w"].mid == P()
