"""Worker program for the REAL multi-process jax.distributed test
(tests/test_fsdp_multihost.py::TestRealMultiProcess). Runs as a fresh
subprocess: platform switch must precede any backend use, exactly like
conftest's recipe.

Usage: python _multihost_worker.py <coordinator> <num_procs> <proc_id>
Exits 0 iff every assertion holds on this process.

``--probe`` mode (PR 5): stop after the topology checks and exit 0
(capable) or 31 (this environment cannot form cross-process DCN device
visibility — jax.devices() does not span hosts). The tier-1 gate uses
it to SKIP the full test with a reason instead of failing on an
environment limitation (tests/test_fsdp_multihost.py).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4)  # 4 local x 2 procs = 8 global

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from distributed_pytorch_tpu.runtime import multihost  # noqa: E402


#: --probe exit code meaning "environment cannot do cross-process DCN".
PROBE_INCAPABLE = 31


def main(coordinator: str, num_procs: int, proc_id: int,
         probe: bool = False) -> int:
    multihost.initialize(coordinator_address=coordinator,
                         num_processes=num_procs, process_id=proc_id)
    if probe:
        ok = (jax.process_count() == num_procs
              and len(jax.devices()) == 4 * num_procs)
        why = (f"process_count={jax.process_count()} "
               f"devices={len(jax.devices())}")
        if ok:
            # topology is not enough: some jaxlib CPU backends form the
            # global device view but refuse cross-process computations
            # ("Multiprocess computations aren't implemented on the CPU
            # backend") — probe an actual cross-process reduction
            try:
                from jax.experimental import multihost_utils
                g = multihost_utils.process_allgather(np.int32(proc_id))
                ok = sorted(np.asarray(g).ravel().tolist()) == list(
                    range(num_procs))
                why = f"allgather={np.asarray(g).ravel().tolist()}"
            except Exception as e:  # noqa: BLE001
                ok = False
                why = f"cross-process compute failed: {e}"
        print(f"probe proc {proc_id}: {why} -> "
              f"{'ok' if ok else 'incapable'}", flush=True)
        return 0 if ok else PROBE_INCAPABLE
    assert jax.process_count() == num_procs, jax.process_count()
    assert multihost.num_hosts() == num_procs
    assert multihost.host_index() == proc_id
    assert multihost.is_primary_host() == (proc_id == 0)
    assert len(jax.devices()) == 4 * num_procs, "global devices span hosts"
    lo, hi = multihost.local_device_slice()
    assert (lo, hi) == (4 * proc_id, 4 * proc_id + 4)

    # dp-over-dcn mesh: outer axis crosses processes, inner stays local
    os.environ["DPX_CPU_DEVICES"] = "all"
    mesh = multihost.init_hybrid_mesh(ici=[("dp", 4)],
                                      dcn=[("dp_outer", num_procs)])
    assert mesh.shape == {"dp_outer": num_procs, "dp": 4}

    # a gradient-averaging DP step over BOTH axes — the collective crosses
    # the process boundary (the thing the reference cannot do at all:
    # its rendezvous is hardcoded localhost, reference distributed.py:48)
    def local_step(w, x):
        g = jax.grad(lambda w: jnp.mean((x * w) ** 2))(w)
        return jax.lax.pmean(jax.lax.pmean(g, "dp"), "dp_outer")

    step = jax.jit(jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), P(("dp_outer", "dp"))),
        out_specs=P(), check_vma=False))

    # global batch 8, one row per global device; every process must supply
    # its addressable shards of the global array
    from jax.experimental import multihost_utils
    xg = np.arange(8, dtype=np.float32)[:, None]
    x = multihost_utils.host_local_array_to_global_array(
        xg[lo:hi], mesh, P(("dp_outer", "dp")))
    g = step(jnp.float32(2.0), x)
    want = float(np.mean(2 * 2.0 * xg ** 2))
    got = float(jax.device_get(g))
    assert abs(got - want) < 1e-5, (got, want)

    # control-plane helpers cross processes too
    gathered = multihost.process_allgather(np.int32(proc_id))
    assert list(np.asarray(gathered).ravel()) == list(range(num_procs))
    b = multihost.broadcast_from_primary(np.int32(proc_id + 41))
    assert int(b) == 41  # process 0's value everywhere

    print(f"proc {proc_id} ok")
    return 0


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if a != "--probe"]
    raise SystemExit(main(args[0], int(args[1]), int(args[2]),
                          probe="--probe" in sys.argv[1:]))
