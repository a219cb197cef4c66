"""One sha256 per program of the benchmark's cells, from the lowered text.

    python tools/lowered_hash.py [--cells a,b] [--against CHECKOUT]

"The lowered programs are the parent's" is the cheapest proof that a
refactor changed nothing a cell runs, and it needs no chip. For each cell
of ``BENCHMARK.json`` (or those named) this builds the cell through its
own kind's ``build`` (``chipbench/kinds/*.py``) under ``jax.eval_shape``,
so that the weights and the stores are shapes and never arrays; lowers the
cell's programs at the real shapes on the CPU backend (a serving cell: the
decode or block-step program and every prefill bucket's; a training cell:
the model's forward); and prints one hash a program of the text as
:func:`normalize` leaves it: without locations, without the module's name,
and with each Mosaic kernel's serialized body printed again without the
paths and line numbers it embeds. ``--against CHECKOUT`` runs the same in a
second tree (``git archive <commit> | tar -x -C <dir>``), with that tree's
code, and prints both hashes beside each other and which differ; it exits
1 where any does.

On the CPU backend the programs take their CPU branches (the decode
attention's loop, ``ragged_dot``): what a Mosaic kernel's call site gets
is in the text, the kernel is not, except where a test or
``lowering_platforms`` puts it there.
"""

from __future__ import annotations

import argparse
import base64
import hashlib
import importlib
import json
import os
import re
import subprocess
import sys

_LOC_DEF = re.compile(r"^#loc\d*\s*=.*\n?", re.M)
_LOC_USE = re.compile(r"\s*loc\((?:[^()\"]|\"[^\"]*\"|\((?:[^()\"]|\"[^\"]*\")*\))*\)")
_MODULE = re.compile(r"^module @\S+", re.M)
_MOSAIC = re.compile(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)')


def _kernel_text(body: str) -> str:
    """A Mosaic kernel's serialized module (base64 of MLIR bytecode,
    which carries the source paths and line numbers of every operation)
    as text without them."""
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib.mlir import ir

    ctx = jmlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(body))
        return module.operation.get_asm(enable_debug_info=False)


def normalize(text: str) -> str:
    """The lowered text of a program without what does not change what
    it computes: where its operations came from, and what it is called."""
    text = _MOSAIC.sub(
        lambda m: m.group(1) + hashlib.sha256(
            _kernel_text(m.group(2)).encode()).hexdigest() + m.group(3), text)
    text = _LOC_DEF.sub("", text)
    text = _LOC_USE.sub("", text)
    return _MODULE.sub("module @_", text)


def program_hash(lowered) -> str:
    """``lowered``: what ``jax.jit(f).lower(...)`` returns."""
    text = normalize(lowered.as_text(debug_info=True))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def cell_programs(root: str, name: str):
    """``(program, lowered)`` for each program of cell ``name``, built by
    the cell's own kind from shapes."""
    import jax
    import jax.numpy as jnp

    from chipbench.run import Cell

    cell = Cell(root, name, seed=0, seconds=0, trace=False)
    kind = importlib.import_module(f"chipbench.kinds.{cell.traffic['kind']}")
    S = jax.ShapeDtypeStruct
    i32 = lambda *shape: S(shape, jnp.int32)
    flag = lambda *shape: S(shape, jnp.bool_)
    built = []
    if cell.traffic["kind"].startswith("train"):
        from distributed_pytorch_tpu import models

        # a training kind's build hands back the compiled step, not the
        # model: the model is taken where the kind constructs it
        made, real = [], models.TransformerLM

        def recording(*a, **kw):
            made.append(real(*a, **kw))
            return made[-1]

        def build():
            models.TransformerLM = recording
            try:
                out = kind.build(cell, jax.devices()[:1], 0)
            finally:
                models.TransformerLM = real
            return out[2]           # the parameters

        params = jax.eval_shape(build)
        rows, seq = cell.traffic["rows_per_chip"], cell.traffic["seq"]
        yield f"forward_{rows}x{seq}", jax.jit(made[0].apply).lower(
            params, i32(rows, seq))
        return

    def build():
        built.append(kind.build(cell, 0))
        pool = built[0].pool
        return (built[0].params, pool.state, pool.moe_counts,
                pool.sel_counts, pool.blocks)

    params, state, counts, sel, blocks = jax.eval_shape(build)
    eng = built[0]
    pool, n = eng.pool, eng.config.n_slots
    tables = i32(n, pool.pages_per_slot)
    if pool.gen_block:
        g = pool.gen_block
        yield "decode_block_step", jax.jit(pool._decode_block).lower(
            params, state, counts, blocks, tables, i32(n), i32(n, g),
            i32(n), i32(n), flag(n))
    else:
        yield "decode", jax.jit(pool._decode).lower(
            params, state, counts, tables, i32(n), i32(n), flag(n), sel)
    dense = flag() if pool.sparse_layers else None
    for bucket in eng.buckets:
        admit = lambda *a, bucket=bucket: pool._admit(*a, bucket=bucket)
        yield f"prefill_b{bucket}", jax.jit(admit).lower(
            params, state, i32(pool.pages_per_slot), i32(1, bucket), i32(),
            i32(), i32(), dense)


def hashes(root: str, cells) -> dict:
    """``{"<cell> <program>": hash}`` for the cells named (all where
    none is), importing ``chipbench`` and the library from ``root``."""
    import jax

    # shapes only, on the CPU backend, wherever this runs
    jax.config.update("jax_platforms", "cpu")
    root = os.path.abspath(root)
    sys.path[:0] = [root]
    os.chdir(root)
    with open("BENCHMARK.json") as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    unknown = sorted(set(cells or ()) - set(names))
    if unknown:
        raise SystemExit(f"no cell {unknown} in BENCHMARK.json")
    out = {}
    for name in names:
        if cells and name not in cells:
            continue
        for program, lowered in cell_programs(root, name):
            out[f"{name} {program}"] = program_hash(lowered)
            print(f"# {name} {program} {out[f'{name} {program}']}",
                  file=sys.stderr, flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap.add_argument("--root", default=here,
                    help="the tree whose programs are lowered")
    ap.add_argument("--cells", default="",
                    help="comma-separated cells (default: all)")
    ap.add_argument("--against", metavar="CHECKOUT",
                    help="a second tree to lower the same programs in")
    ap.add_argument("--json", action="store_true",
                    help="print one JSON object instead of lines")
    args = ap.parse_args(argv)
    cells = [c for c in args.cells.split(",") if c]
    theirs = None
    if args.against:
        theirs = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--root",
             args.against, "--cells", args.cells, "--json"],
            stdout=subprocess.PIPE, text=True)
    mine = hashes(args.root, cells)
    if theirs is None:
        print(json.dumps(mine, indent=1) if args.json else
              "\n".join(f"{h}  {k}" for k, h in mine.items()))
        return 0
    out, _ = theirs.communicate()
    if theirs.returncode:
        raise SystemExit(f"lowering in {args.against} failed")
    other = json.loads(out)
    both = list(mine) + [k for k in other if k not in mine]
    differ = 0
    print(f"{'here':16}  {'against':16}  program")
    for k in both:
        a, b = mine.get(k, "-"), other.get(k, "-")
        differ += a != b
        print(f"{a:16}  {b:16}  {k}{'' if a == b else '   DIFFERS'}")
    print(f"{differ} of {len(both)} programs differ")
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main())
