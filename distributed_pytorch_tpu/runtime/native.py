"""ctypes bindings for the native host runtime (native/dpxhost.cpp) —
the c10d-TCPStore/Gloo replacement (SURVEY.md §2.3 rows 2-3).

Auto-builds ``libdpxhost.so`` with g++ on first use when it is missing
or was built from another ``dpxhost.cpp`` than the one in the checkout
(no pip/pybind dependency; pure C ABI + ctypes).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

from . import env as _envreg

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_SRC_PATH = os.path.join(_NATIVE_DIR, "dpxhost.cpp")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libdpxhost.so")
#: sha256 of the dpxhost.cpp the library beside it was built from.
_DIGEST_PATH = _LIB_PATH + ".sha256"

_lib = None
_lib_lock = threading.Lock()

#: Env var: per-collective deadline in ms for the native host group
#: (0 disables). Finite by default — a wedged peer must become a typed
#: error, never an infinite hang.
COMM_TIMEOUT_ENV = "DPX_COMM_TIMEOUT_MS"
#: Alias of the registry's declared default (runtime/env.py is the
#: single source of truth for the value; this name is the public export).
DEFAULT_COMM_TIMEOUT_MS = _envreg.REGISTRY[COMM_TIMEOUT_ENV].default

#: Native error codes (mirror dpxhost.cpp's constants).
_RC_PEER_CLOSED = -2
_RC_TIMEOUT = -3
_RC_CORRUPT = -4


class CommError(RuntimeError):
    """A native host collective failed.

    Base of the typed failure hierarchy (ISSUE 2): carries enough to
    *attribute* the failure — which rank raised, which op, and (when the
    transport could tell) which peer is to blame — so supervisors and
    elastic restart logic can act on structure instead of grepping
    message strings.
    """

    def __init__(self, msg: str, *, op: str = "", rank: int = -1,
                 peer: int = -1):
        super().__init__(msg)
        self.op = op
        self.rank = rank
        self.peer = peer


class CommPeerDied(CommError):
    """A peer closed its end mid-collective (orderly close, reset, or
    the abort-propagation teardown of a failed rank)."""


class CommTimeout(CommError):
    """The per-op deadline (``DPX_COMM_TIMEOUT_MS``) elapsed — the peer
    is wedged or the link stalled, but nothing closed."""

    def __init__(self, msg: str, *, deadline_ms: int = 0, **kw):
        super().__init__(msg, **kw)
        self.deadline_ms = deadline_ms


class CommCorrupt(CommError):
    """A framed quantized payload failed its CRC32 integrity check —
    transport or codec corruption that must never reach gradients."""


class CommRetryExhausted(CommError):
    """A TRANSIENT fault outlived the bounded retry budget
    (``DPX_RETRY_MAX`` attempts with ``DPX_RETRY_BACKOFF_MS``
    exponential backoff — ``runtime/chaos.py``). Carries how many
    attempts were made, so a supervisor can distinguish "flaky but we
    tried" from a first-strike failure; the final transient error is
    chained as ``__cause__``."""

    def __init__(self, msg: str, *, attempts: int = 0, **kw):
        super().__init__(msg, **kw)
        self.attempts = attempts


def _source_digest() -> str:
    with open(_SRC_PATH, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _build() -> None:
    # Build to a per-pid temp path and rename atomically: concurrently
    # spawned rank processes may all see the .so missing, and a partially
    # written file must never be dlopen'd.
    digest = _source_digest()
    tmp = f"{_LIB_PATH}.{os.getpid()}.tmp"
    # flags mirror native/Makefile: -fno-math-errno (NOT fast-math) keeps
    # the quantized codec bit-identical to comm/wire.py while letting
    # lrintf/fabsf inline and the quant loops vectorize
    subprocess.run(
        ["g++", "-O3", "-fno-math-errno", "-fPIC", "-std=c++17", "-shared",
         "-o", tmp, _SRC_PATH],
        check=True, capture_output=True)
    os.replace(tmp, _LIB_PATH)
    # the digest lands after the library it describes, as atomically
    with open(tmp, "w") as f:
        f.write(digest)
    os.replace(tmp, _DIGEST_PATH)


def _needs_build() -> bool:
    """Missing, or built from other source than the checkout holds. The
    key is the CONTENT of dpxhost.cpp (its sha256, recorded beside the
    library at build time), never a timestamp: a copied tree carries the
    untracked binary along with whatever mtimes the copy gave it, and an
    old library would silently lack new symbols."""
    try:
        with open(_DIGEST_PATH) as f:
            built_from = f.read().strip()
    except OSError:
        return True
    return not os.path.exists(_LIB_PATH) or built_from != _source_digest()


def load_library():
    """Load (building if needed) the native library; idempotent.

    ``DPX_NATIVE_LIB`` overrides the library path entirely (no
    auto-build): the CI sanitizer jobs point it at an ASan/UBSan/TSan
    build of the same source (``make -C native asan``) so the whole
    test suite exercises the instrumented library (docs/analysis.md)."""
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        override = _envreg.get("DPX_NATIVE_LIB")
        if override:
            lib = ctypes.CDLL(override)
        else:
            if _needs_build():
                _build()
            lib = ctypes.CDLL(_LIB_PATH)
        lib.dpx_comm_init.restype = ctypes.c_void_p
        lib.dpx_comm_init.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                      ctypes.c_int, ctypes.c_int,
                                      ctypes.c_int]
        lib.dpx_comm_destroy.argtypes = [ctypes.c_void_p]
        lib.dpx_rank.argtypes = [ctypes.c_void_p]
        lib.dpx_rank.restype = ctypes.c_int
        lib.dpx_world.argtypes = [ctypes.c_void_p]
        lib.dpx_world.restype = ctypes.c_int
        lib.dpx_allreduce_f32.argtypes = [ctypes.c_void_p,
                                          ctypes.POINTER(ctypes.c_float),
                                          ctypes.c_int64]
        lib.dpx_allreduce_f32.restype = ctypes.c_int
        lib.dpx_allreduce_f64.argtypes = [ctypes.c_void_p,
                                          ctypes.POINTER(ctypes.c_double),
                                          ctypes.c_int64]
        lib.dpx_allreduce_f64.restype = ctypes.c_int
        lib.dpx_allreduce_f32_op.argtypes = [ctypes.c_void_p,
                                             ctypes.POINTER(ctypes.c_float),
                                             ctypes.c_int64, ctypes.c_int]
        lib.dpx_allreduce_f32_op.restype = ctypes.c_int
        lib.dpx_allreduce_f64_op.argtypes = [ctypes.c_void_p,
                                             ctypes.POINTER(ctypes.c_double),
                                             ctypes.c_int64, ctypes.c_int]
        lib.dpx_allreduce_f64_op.restype = ctypes.c_int
        lib.dpx_allreduce_q8.argtypes = [ctypes.c_void_p,
                                         ctypes.POINTER(ctypes.c_float),
                                         ctypes.c_int64, ctypes.c_int,
                                         ctypes.c_int]
        lib.dpx_allreduce_q8.restype = ctypes.c_int
        for name in ("dpx_reduce_scatter_q8", "dpx_allgather_q8"):
            fn = getattr(lib, name)
            fn.argtypes = [ctypes.c_void_p,
                           ctypes.POINTER(ctypes.c_float),
                           ctypes.c_int64, ctypes.c_int, ctypes.c_int]
            fn.restype = ctypes.c_int
        # width-parameterized quantized ring family (trailing int =
        # wire bits, 8 or 4 — the adaptive wire's native face)
        for name in ("dpx_allreduce_qn", "dpx_reduce_scatter_qn",
                     "dpx_allgather_qn"):
            fn = getattr(lib, name)
            fn.argtypes = [ctypes.c_void_p,
                           ctypes.POINTER(ctypes.c_float),
                           ctypes.c_int64, ctypes.c_int, ctypes.c_int,
                           ctypes.c_int]
            fn.restype = ctypes.c_int
        lib.dpx_reduce_f32.argtypes = [ctypes.c_void_p,
                                       ctypes.POINTER(ctypes.c_float),
                                       ctypes.c_int64]
        lib.dpx_reduce_f32.restype = ctypes.c_int
        lib.dpx_gather.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_int64, ctypes.c_char_p]
        lib.dpx_gather.restype = ctypes.c_int
        lib.dpx_broadcast.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_int64, ctypes.c_int]
        lib.dpx_broadcast.restype = ctypes.c_int
        lib.dpx_barrier.argtypes = [ctypes.c_void_p]
        lib.dpx_barrier.restype = ctypes.c_int
        lib.dpx_set_timeout_ms.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.dpx_set_timeout_ms.restype = None
        lib.dpx_get_timeout_ms.argtypes = [ctypes.c_void_p]
        lib.dpx_get_timeout_ms.restype = ctypes.c_int
        lib.dpx_last_error_peer.argtypes = [ctypes.c_void_p]
        lib.dpx_last_error_peer.restype = ctypes.c_int
        lib.dpx_comm_abort.argtypes = [ctypes.c_void_p]
        lib.dpx_comm_abort.restype = None
        lib.dpx_crc32c.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.dpx_crc32c.restype = ctypes.c_uint32
        _lib = lib
        return lib


def crc32c(buf) -> int:
    """CRC32C (Castagnoli) of a bytes-like buffer via the native library —
    the PR 2 checksum vocabulary (hw sse4.2 when available, bit-identical
    sw slice-by-4 otherwise). Accepts bytes/bytearray/memoryview or a
    C-contiguous numpy array. Raises OSError/CalledProcessError when the
    native build is impossible; callers needing a no-compiler fallback use
    :func:`distributed_pytorch_tpu.ckpt.integrity.crc32c`."""
    lib = load_library()
    if not isinstance(buf, np.ndarray):
        buf = np.frombuffer(memoryview(buf), dtype=np.uint8)
    if not buf.flags.c_contiguous:
        buf = np.ascontiguousarray(buf)
    if buf.nbytes == 0:
        return int(lib.dpx_crc32c(None, 0))
    return int(lib.dpx_crc32c(
        buf.ctypes.data_as(ctypes.c_void_p), buf.nbytes))


class HostComm:
    """A native per-process communicator (one per rank OS process).

    The process-group object of the per-rank-process front door: ring
    allreduce + hub rooted collectives over localhost TCP, rendezvoused on
    ``base_port`` (the MASTER_PORT analog, reference distributed.py:48-49).
    """

    #: allreduce op codes (mirror dpxhost.cpp's enum)
    _OPS = {"sum": 0, "max": 1, "min": 2}

    def __init__(self, master_addr: str, base_port: int, rank: int,
                 world: int, timeout_ms: int = 30000,
                 op_timeout_ms: Optional[int] = None):
        import socket as _socket

        # late imports: runtime/__init__ imports this module eagerly, and
        # comm/__init__ imports runtime.context — binding here (after all
        # packages finished loading) avoids the cycle
        from . import faults as _faults
        from ..analysis.schedule import RankSchedule
        from ..comm import wire as _wire
        from ..obs import trace as _dpxtrace
        from ..utils.profiler import CommStats

        self._dpxtrace = _dpxtrace
        # every span this process records from here on is rank-attributed
        _dpxtrace.set_rank(rank)

        self._wire = _wire
        self._faults = _faults
        self.stats = CommStats()
        # dpxmon (obs/metrics.py): rank-stamp the metrics registry and
        # register this comm's per-op accounting as the pull-model
        # `comm` provider — snapshots carry op counts/bytes and the
        # exposed-vs-overlapped split with zero hot-path cost (polled
        # once per snapshot; re-registration replaces a dead comm's)
        from ..obs import metrics as _dpxmon
        _dpxmon.set_rank(rank)
        _dpxmon.register_provider("comm", self.stats.monitor_metrics)
        # always-on collective-schedule recorder: every issued op folds
        # into a rolling per-rank digest so a cross-rank divergence is
        # reportable as "rank R issued X where peers issued Y at seq N"
        # instead of a bare CommTimeout (analysis/schedule.py)
        self.schedule = RankSchedule(rank=rank, world=world)
        self._lib = load_library()
        # the native layer takes dotted-quad only; resolve hostnames (e.g.
        # 'localhost', the reference's MASTER_ADDR default) here
        addr = _socket.gethostbyname(master_addr)

        def _rendezvous():
            # the op=init fault hook fires per ATTEMPT (flaky@op=init
            # proves the retry path); a null handle is the native
            # layer's connect/accept failure after its own internal
            # timeout — nothing is established yet, so re-entering is
            # safe, and rendezvous is the one comm call that retries
            # (docs/failures.md "Retry policy")
            _faults.on_comm_op("init", rank=rank)
            h = self._lib.dpx_comm_init(
                addr.encode(), base_port, rank, world, timeout_ms)
            if not h:
                raise CommError(
                    f"native rendezvous failed (rank {rank}/{world} on "
                    f"{master_addr}:{base_port})", op="init", rank=rank)
            return h

        from . import chaos as _chaos
        self._h = _chaos.call_with_retry(
            _rendezvous, op="init", rank=rank,
            transient=(_faults.FlakyFault, CommError))
        if op_timeout_ms is None:
            op_timeout_ms = _envreg.get(COMM_TIMEOUT_ENV)
        self._lib.dpx_set_timeout_ms(self._h, op_timeout_ms)
        self.op_timeout_ms = op_timeout_ms
        self.rank = rank
        self.world = world
        # remembered so derived sub-communicators (the hierarchical
        # ring's local/leader groups, comm/hier.py) can rendezvous on
        # deterministic ports relative to this group's
        self.master_addr = master_addr
        self.base_port = base_port
        self._hier_ring = None   # comm.hier.hier_ring() cache
        # dpxverify's dynamic half (comm/sanitizer.py): armed, every
        # collective first exchanges a fingerprint and a divergence is
        # a typed CollectiveMismatch within one exchange; unarmed, the
        # whole feature is the `is None` test in _pre_op
        self._sanitizer = None
        if _envreg.get("DPX_COMM_SANITIZE"):
            from ..comm.sanitizer import CollectiveSanitizer
            self._sanitizer = CollectiveSanitizer(self)
        _faults.register_comm(self)

    def close(self):
        ring = getattr(self, "_hier_ring", None)
        self._hier_ring = None
        if ring is not None:
            ring.close()
        if self._h:
            self._lib.dpx_comm_destroy(self._h)
            self._h = None

    def abort(self):
        """Tear down every link of this comm NOW (without destroying the
        handle): blocked peers observe peer-closed within one deadline
        tick, and every later op on this comm raises :class:`CommError`.
        Called on local failure (abort propagation) and by fault
        injection's ``drop_conn``."""
        if self._h:
            self._lib.dpx_comm_abort(self._h)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def _pre_op(self, op: str, *, dtype: str = "", size: int = 0,
                extra: str = ""):
        """Per-op entry hook: fault injection first (an injected
        divergent collective must land in the schedule at ITS issue
        point), then the schedule recorder folds this op's signature
        into the rolling digest; the sanitizer exchange runs LAST so a
        diverging op is already in the flushed window when it raises."""
        self._faults.on_comm_op(op, rank=self.rank, comm=self)
        self.schedule.record(op, dtype=dtype, size=size, extra=extra)
        if self._sanitizer is not None:
            self._sanitizer.check(op, dtype=dtype, size=size)

    def _check(self, rc: int, what: str):
        if rc == 0:
            return
        # a failing collective flushes this rank's recent schedule to the
        # line-JSON event log BEFORE raising, so the cross-rank verifier
        # can name the diverging op/rank (analysis/schedule.py) — never
        # allowed to mask the real typed error
        self.schedule.flush(op=what)
        peer = self._lib.dpx_last_error_peer(self._h) if self._h else -1
        where = f"(rank {self.rank}, op {what}"
        where += f", peer {peer})" if peer >= 0 else ")"
        if rc == _RC_PEER_CLOSED:
            exc = CommPeerDied(
                f"peer closed connection mid-collective {where}",
                op=what, rank=self.rank, peer=peer)
        elif rc == _RC_TIMEOUT:
            exc = CommTimeout(
                f"deadline {self.op_timeout_ms}ms exceeded {where}",
                op=what, rank=self.rank, peer=peer,
                deadline_ms=self.op_timeout_ms)
        elif rc == _RC_CORRUPT:
            exc = CommCorrupt(
                f"framed quant payload failed CRC32 {where}",
                op=what, rank=self.rank, peer=peer)
        else:
            exc = CommError(f"native {what} failed {where} rc={rc}",
                            op=what, rank=self.rank, peer=peer)
        # flight recorder: the last-N spans of this rank's timeline ride
        # out alongside the typed error (obs/trace.py) — the postmortem
        # every chaos survivor ships; best-effort, never masks `exc`
        self._dpxtrace.on_typed_failure(exc)
        raise exc

    def allreduce(self, arr: np.ndarray, op: str = "sum",
                  hidden: bool = False) -> np.ndarray:
        """In-place ring allreduce on a float32/float64 array.

        ``op``: ``sum`` (the classic ring) or elementwise ``max``/``min``
        — same ring, same 2*(W-1)/W bytes per rank (the max/min path used
        to all-gather the whole tensor from every rank, W x the traffic).
        ``hidden``: account the comm time as overlapped with
        still-running backward compute (CommStats).
        """
        if op not in self._OPS:
            raise ValueError(f"allreduce op must be sum|max|min, got {op!r}")
        arr = np.ascontiguousarray(arr)
        self._pre_op("allreduce", dtype=str(arr.dtype), size=int(arr.size),
                     extra=op)
        code = self._OPS[op]
        nbytes = self._wire.ring_allreduce_wire_bytes(
            arr.size, self.world, arr.dtype.itemsize) // max(self.world, 1)
        with self.stats.timed(f"allreduce_{op}", nbytes, hidden=hidden):
            if arr.dtype == np.float32:
                rc = self._lib.dpx_allreduce_f32_op(
                    self._h,
                    arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                    arr.size, code)
            elif arr.dtype == np.float64:
                rc = self._lib.dpx_allreduce_f64_op(
                    self._h,
                    arr.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                    arr.size, code)
            else:
                raise TypeError(
                    f"allreduce supports f32/f64, got {arr.dtype}")
        self._check(rc, "allreduce")
        return arr

    def allreduce_quant(self, arr: np.ndarray, bits: int = 8,
                        block: int = None, chunk_blocks: int = None,
                        hidden: bool = False) -> np.ndarray:
        """In-place QUANTIZED ring allreduce (sum) on a float32 array at
        a selectable wire width.

        Block-scaled wire format (comm/wire.py), chunk-pipelined and
        double-buffered (chunk i+1 quantizes while chunk i is on the
        wire); LOSSY (one quantization step per hop) but bit-identical
        across ranks. ``bits=8``: ~4x less wire traffic than
        :meth:`allreduce`; ``bits=4``: ~7.9x (nibble-packed), at ~18x
        the per-hop rounding error — pick per bucket with
        :class:`~..comm.wire.WidthChooser`. The op is recorded as
        ``allreduce_q8``/``allreduce_q4``, so a cross-rank width
        disagreement shows up as a schedule divergence, not silent
        corruption. ``hidden``: account the comm time as overlapped
        with still-running backward compute (CommStats)."""
        block = block or self._wire.QUANT_BLOCK
        chunk_blocks = chunk_blocks or self._wire.QUANT_CHUNK_BLOCKS
        self._wire.quant_levels(bits)
        op = f"allreduce_q{bits}"
        arr = np.ascontiguousarray(arr, dtype=np.float32)
        self._pre_op(op, dtype="float32", size=int(arr.size),
                     extra=f"block={block}")
        nbytes = self._wire.quant_ring_allreduce_wire_bytes(
            arr.size, self.world, block, bits) // max(self.world, 1)
        with self.stats.timed(op, nbytes, hidden=hidden):
            rc = self._lib.dpx_allreduce_qn(
                self._h, arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                arr.size, block, chunk_blocks, bits)
        self._check(rc, op)
        return arr

    def allreduce_q8(self, arr: np.ndarray, block: int = None,
                     chunk_blocks: int = None,
                     hidden: bool = False) -> np.ndarray:
        """:meth:`allreduce_quant` at the historical 8-bit width."""
        return self.allreduce_quant(arr, 8, block, chunk_blocks,
                                    hidden=hidden)

    def allreduce_q4(self, arr: np.ndarray, block: int = None,
                     chunk_blocks: int = None,
                     hidden: bool = False) -> np.ndarray:
        """:meth:`allreduce_quant` at the 4-bit (nibble-packed) width —
        a named method so the static schedule extractor sees the q4 op
        at its call sites (analysis/schedule.py NATIVE_OPS)."""
        return self.allreduce_quant(arr, 4, block, chunk_blocks,
                                    hidden=hidden)

    def reduce_scatter_quant(self, arr: np.ndarray, bits: int = 8,
                             block: int = None, chunk_blocks: int = None,
                             hidden: bool = False) -> np.ndarray:
        """In-place QUANTIZED ring reduce-scatter (sum) on a float32
        array — the first leg of :meth:`allreduce_quant` alone.

        On return, this rank's :func:`~..comm.wire.ring_owned_span`
        holds the reduced sum; every other span holds a partial
        accumulation (undefined). Half the allreduce's wire bytes. The
        weight-update half of the ZeRO-1 recipe runs between this and
        :meth:`allgather_quant` (optim/sharded/)."""
        block = block or self._wire.QUANT_BLOCK
        chunk_blocks = chunk_blocks or self._wire.QUANT_CHUNK_BLOCKS
        self._wire.quant_levels(bits)
        arr = np.ascontiguousarray(arr, dtype=np.float32)
        self._pre_op("reduce_scatter", dtype="float32",
                     size=int(arr.size), extra=f"q{bits},block={block}")
        nbytes = self._wire.quant_leg_wire_bytes(
            arr.size, self.world, block, bits) // max(self.world, 1)
        with self.stats.timed("reduce_scatter", nbytes, hidden=hidden):
            rc = self._lib.dpx_reduce_scatter_qn(
                self._h, arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                arr.size, block, chunk_blocks, bits)
        self._check(rc, "reduce_scatter")
        return arr

    def reduce_scatter_q8(self, arr: np.ndarray, block: int = None,
                          chunk_blocks: int = None) -> np.ndarray:
        """:meth:`reduce_scatter_quant` at the historical 8-bit width."""
        return self.reduce_scatter_quant(arr, 8, block, chunk_blocks)

    def allgather_quant(self, arr: np.ndarray, bits: int = 8,
                        block: int = None, chunk_blocks: int = None,
                        hidden: bool = False) -> np.ndarray:
        """In-place QUANTIZED ring all-gather on a float32 array — the
        byte-forwarding second leg of :meth:`allreduce_quant` alone.

        This rank contributes its :func:`~..comm.wire.ring_owned_span`;
        afterwards the full buffer is BIT-IDENTICAL on every rank (each
        span decodes its owner's forwarded bytes, owner included)."""
        block = block or self._wire.QUANT_BLOCK
        chunk_blocks = chunk_blocks or self._wire.QUANT_CHUNK_BLOCKS
        self._wire.quant_levels(bits)
        arr = np.ascontiguousarray(arr, dtype=np.float32)
        self._pre_op("allgather", dtype="float32", size=int(arr.size),
                     extra=f"q{bits},block={block}")
        nbytes = self._wire.quant_leg_wire_bytes(
            arr.size, self.world, block, bits) // max(self.world, 1)
        with self.stats.timed("allgather", nbytes, hidden=hidden):
            rc = self._lib.dpx_allgather_qn(
                self._h, arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                arr.size, block, chunk_blocks, bits)
        self._check(rc, "allgather")
        return arr

    def allgather_q8(self, arr: np.ndarray, block: int = None,
                     chunk_blocks: int = None) -> np.ndarray:
        """:meth:`allgather_quant` at the historical 8-bit width."""
        return self.allgather_quant(arr, 8, block, chunk_blocks)

    def owned_span(self, n: int, block: int = None):
        """(offset, count) of the flat span this rank owns after
        :meth:`reduce_scatter_q8` of an n-element buffer."""
        block = block or self._wire.QUANT_BLOCK
        return self._wire.ring_owned_span(n, self.world, self.rank, block)

    def reduce(self, arr: np.ndarray) -> np.ndarray:
        """Rooted sum to rank 0 (non-root buffers unchanged)."""
        arr = np.ascontiguousarray(arr, dtype=np.float32)
        self._pre_op("reduce", dtype="float32", size=int(arr.size))
        with self.stats.timed("reduce", arr.nbytes):
            rc = self._lib.dpx_reduce_f32(
                self._h, arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                arr.size)
        self._check(rc, "reduce")
        return arr

    def gather(self, arr: np.ndarray) -> Optional[list]:
        """Rooted gather to rank 0: returns the list there, None elsewhere."""
        arr = np.ascontiguousarray(arr)
        self._pre_op("gather", dtype=str(arr.dtype), size=int(arr.size))
        nbytes = arr.nbytes
        with self.stats.timed("gather", nbytes):
            if self.rank == 0:
                recv = np.zeros((self.world,) + arr.shape, dtype=arr.dtype)
                rc = self._lib.dpx_gather(
                    self._h, arr.tobytes(), nbytes,
                    recv.ctypes.data_as(ctypes.c_char_p))
                self._check(rc, "gather")
                return [recv[r] for r in range(self.world)]
            rc = self._lib.dpx_gather(self._h, arr.tobytes(), nbytes, None)
        self._check(rc, "gather")
        return None

    def all_gather(self, arr: np.ndarray) -> np.ndarray:
        """Every rank gets the stacked (world, *shape) values (gather to
        the hub + broadcast)."""
        arr = np.ascontiguousarray(arr)
        if self.rank == 0:
            stacked = np.stack(self.gather(arr))
        else:
            self.gather(arr)
            stacked = np.zeros((self.world,) + arr.shape, dtype=arr.dtype)
        return self.broadcast(stacked, src=0)

    def broadcast(self, arr: np.ndarray, src: int = 0) -> np.ndarray:
        arr = np.ascontiguousarray(arr)
        self._pre_op("broadcast", dtype=str(arr.dtype), size=int(arr.size),
                     extra=f"src={src}")
        with self.stats.timed("broadcast", arr.nbytes):
            rc = self._lib.dpx_broadcast(
                self._h, arr.ctypes.data_as(ctypes.c_char_p), arr.nbytes,
                src)
        self._check(rc, "broadcast")
        return arr

    def barrier(self):
        self._pre_op("barrier")
        with self.stats.timed("barrier", 4):
            rc = self._lib.dpx_barrier(self._h)
        self._check(rc, "barrier")
