"""Test harness: an 8-device virtual CPU mesh.

This is the multi-node-without-a-cluster strategy from SURVEY.md §4: XLA's
host platform exposes N virtual devices in one process, so every mesh/
collective/parallelism test runs on any machine and exercises the same SPMD
code paths that run on a TPU pod.

The platform and the device count are set through jax.config *before any
backend is initialized*, so the suite is CPU-only whatever the environment
says. DPX_CPU_DEVICES opts the virtual devices in as 'accelerators' for the
framework's device discovery (see runtime/context.py).
"""

import os
import sys

# repo root on sys.path so `examples.` and top-level modules import
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
os.environ.setdefault("DPX_CPU_DEVICES", "8")

import pytest  # noqa: E402

import distributed_pytorch_tpu as dist  # noqa: E402

assert jax.device_count() == 8, "virtual CPU mesh failed to initialize"


@pytest.fixture(autouse=True)
def clean_group():
    """Every test starts and ends without a live process group."""
    dist.cleanup()
    yield
    dist.cleanup()


@pytest.fixture
def group8():
    """An initialized 8-way dp group on the virtual CPU mesh."""
    dist.init_process_group(rank=0, world_size=8)
    yield 8
    dist.cleanup()
