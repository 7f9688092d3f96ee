#!/usr/bin/env python
"""What in a serving configuration's compiled programs moves a whole
embedding table (PR 46): ``jit_step`` and ``jit_prefill`` of the engine
the benchmark's driver builds for ``--config``, compiled by the chip's
compiler, and of the OPTIMISED HLO every operation whose result or one
of whose operands is at least one table in size and that is not a
product.

    chiprun --chips 1 -- python scripts/step_relayout_probe.py --config gpt2_xl --pages 32
    python scripts/step_relayout_probe.py --config gpt2_xl --set n_layer=2 --describe

A table is a matrix of the weights with one row a token id (``vocab``
rows: the embedding, and the head where it is untied); the least of
them sets the size.  Listed, one line each with the program, the
operation's name and kind, its result's and operands' shapes WITH
their layouts and tilings, and the ``op_name`` the tracer gave it:
copies, transposes, and every other fusion.  Counted and not listed:
a product over a table (the head's: ``products``), a write into a
buffer where it lies (a fusion whose root is a ``scatter`` or a
``dynamic-update-slice``: the cache's rows; ``in_place``), a Pallas
kernel that is handed a pool (``kernels``), and a ``gather`` or a
``dynamic-slice`` whose result is smaller than a table (``row_reads``:
it is handed the table and reads the rows it picks).  An empty list is
what every configuration wants: a step then reads of a table the rows
it gathers.

The weights are the reference's from ``--seed``, the engine the
driver's own (``benchmarks/drivers``), the arguments the engine's own
at ``--pages`` pages a slot and the ``--buckets`` prompt lengths, as
``tests/test_tpu_lowering.py`` lowers them; ``--set key=value``
overrides a key of the configuration (a depth, for a rehearsal).
``--describe`` compiles for a v5e that is described and not attached
(``jax.experimental.topologies``): the same compiler, no chip, and so
on the CPU only with ``--set`` cut to a size the sandbox holds.  Writes
``chiprun_out/step_relayout_probe/<config>.json``.  Exit 2 unless the
backend is a TPU (or ``--describe``).  Nothing a cell runs imports this
file.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ITEM = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
        "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
        "u64": 8}
#: an array in an instruction's text: element type, dimensions, layout
ARRAY = re.compile(r"\b(%s)\[([\d,]*)\](\{[^}]*\})?" % "|".join(ITEM))
INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")
#: kinds that move nothing themselves
PASSES = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
          "while", "conditional", "call", "copy-start", "after-all",
          "partition-id", "replica-id"}
PRODUCTS = {"convolution", "dot", "ragged-dot"}
IN_PLACE = {"scatter", "dynamic-update-slice"}
ROW_READS = {"gather", "dynamic-slice"}


def nbytes(kind: str, dims: str) -> int:
    n = ITEM[kind]
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def largest(sides) -> int:
    """Bytes of the largest array the texts ``sides`` name."""
    return max((nbytes(a.group(1), a.group(2)) for side in sides
                for a in ARRAY.finditer(side)), default=0)


def computations(text: str) -> dict:
    """name -> its instructions' lines, of an HLO module's text."""
    out, name = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if m:
            name = m.group(1)
            out[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            out[name].append(line)
    return out


def table_sized(text: str, table_bytes: int) -> dict:
    """The operations of a compiled program that touch ``table_bytes``
    or more at once, by what they are (the module's docstring)."""
    comps = {name: [m.groups() + (line,) for m, line in
                    ((INSTR.match(line), line) for line in lines) if m]
             for name, lines in computations(text).items()}
    kinds = {name: {i[2] for i in instrs} for name, instrs in comps.items()}
    roots = {name: i[2] for name, instrs in comps.items() for i in instrs
             if i[4].lstrip().startswith("ROOT")}
    fused = set(re.findall(r"calls=%?([\w.\-]+)", text))
    listed = []
    counts = {"products": 0, "in_place": 0, "kernels": 0, "row_reads": 0}
    for name, instrs in comps.items():
        if name in fused:
            continue
        # an operand is printed by its name alone: its shape is where
        # the computation defined it
        shapes = {i[0]: i[1] for i in instrs}
        for op, result, kind, rest, line in instrs:
            if kind in PASSES:
                continue
            operands = [shapes[n] for n in re.findall(
                r"%([\w.\-]+)", rest.split(")", 1)[0]) if n in shapes]
            wrote, read = largest([result]), largest(operands)
            if max(wrote, read) < table_bytes:
                continue
            called = re.search(r"calls=%?([\w.\-]+)", line)
            inner = kinds.get(called.group(1), {kind}) if called else {kind}
            root = roots.get(called.group(1)) if called else kind
            scope = re.search(r'op_name="([^"]*)"', line)
            # one row of a prompt against the head is a multiply and a
            # reduce, a product by its op_name alone
            if inner & PRODUCTS or (scope and "dot_general" in scope.group(1)):
                counts["products"] += 1
            elif kind == "custom-call":
                counts["kernels"] += 1
            elif root in IN_PLACE:
                counts["in_place"] += 1
            elif wrote < table_bytes and inner & ROW_READS:
                counts["row_reads"] += 1
            else:
                listed.append({
                    "op": op, "kind": kind,
                    "result": [a.group(0) for a in ARRAY.finditer(result)],
                    "operands": [a.group(0) for side in operands
                                 for a in ARRAY.finditer(side)],
                    "op_name": scope.group(1) if scope else ""})
    return {"listed": listed, **counts}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="a name under benchmarks/configs")
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=VALUE", help="override a key of the "
                    "configuration (JSON value)")
    ap.add_argument("--pages", type=int, default=None,
                    help="pages a slot the step names (default: the "
                    "cache's widest table)")
    ap.add_argument("--buckets", default="128,256")
    ap.add_argument("--seed", type=int, default=46)
    ap.add_argument("--describe", action="store_true",
                    help="compile for a described v5e, without a chip")
    ap.add_argument("--out", default="chiprun_out/step_relayout_probe")
    a = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    if a.describe:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.drivers import serve, serve_lm
    from benchmarks.lib import harness

    dev = jax.devices()[0]
    print(f"platform {dev.platform} kind {dev.device_kind} count "
          f"{jax.device_count()}", flush=True)
    if dev.platform != "tpu" and not a.describe:
        print("no TPU: compile for one with --describe", flush=True)
        return 2
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           a.config + ".json"), encoding="utf-8") as fh:
        config = json.load(fh)
    for item in a.set:
        key, _, value = item.partition("=")
        config[key] = json.loads(value)

    ref = harness.reference_for(config)
    sizes = ref.sizes_of(config)
    params = ref.init_params(
        a.seed, sizes, jnp.dtype(config["assumed"]["serving_dtype"]))
    if config["kind"] == "serve":
        eng = serve.build_engine(config, params, sizes)
    else:
        eng = serve_lm.build_engine(config, params)
    weights = eng.weights()
    tables = [leaf for leaf in jax.tree.leaves(weights)
              if leaf.ndim == 2 and leaf.shape[0] >= sizes["vocab"]]
    table_bytes = min(t.size * t.dtype.itemsize for t in tables)
    print(f"{a.config}: tables {[(t.shape, t.dtype.name) for t in tables]}"
          f", the least {table_bytes / 1e6:.1f} MB", flush=True)

    chip = None
    if a.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        # the models ask the backend whether a kernel is interpreted
        jax.default_backend = lambda: "tpu"

    def shaped(args):
        """The arguments' shapes alone (on the described chip)."""
        def one(x):
            x = x if hasattr(x, "dtype") else np.asarray(x)
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)
        return jax.tree.map(one, args)

    pages = a.pages or eng.cache.max_pages_per_slot
    tabs, lengths = eng.cache.device_tables(pages=pages)
    # no slot runs: the kind's own host arrays, all zeros
    host = eng._kind.host_args((), jax.random.key(0))
    programs = {f"step.pages{pages}": eng._step_fn.lower(*shaped((
        weights, *eng.cache.buffers(), tabs, lengths, *eng._carry,
        *host)))}
    for bucket in (int(b) for b in a.buckets.split(",") if b):
        programs[f"prefill{bucket}"] = eng._prefill_fn(bucket).lower(
            *shaped((weights, *eng.cache.buffers(),
                     jnp.zeros((1, bucket), jnp.int32), np.int32(5),
                     jnp.zeros((bucket // eng.page_size,), jnp.int32),
                     np.float32(0.0), jax.random.key(1), np.int32(1),
                     *eng._carry[:eng._kind.handed])))
    eng.close()

    os.makedirs(a.out, exist_ok=True)
    result = {"config": a.config, "set": a.set, "described": a.describe,
              "device": {"platform": dev.platform, "kind": dev.device_kind},
              "tables": [[list(t.shape), t.dtype.name] for t in tables],
              "table_bytes": table_bytes, "programs": {}}
    for name, lowered in programs.items():
        compiled = lowered.compile()
        text = compiled.as_text()
        with open(os.path.join(a.out, f"{a.config}.{name}.hlo.txt"), "w",
                  encoding="utf-8") as fh:
            fh.write(text)
        found = table_sized(text, table_bytes)
        found["temp_bytes"] = compiled.memory_analysis().temp_size_in_bytes
        result["programs"][name] = found
        print(f"{name}: {len(found['listed'])} table-sized operations "
              f"that are no product (products {found['products']}, in "
              f"place {found['in_place']}, kernels {found['kernels']}, "
              f"row reads {found['row_reads']}), temporaries "
              f"{found['temp_bytes'] / 1e6:.1f} MB", flush=True)
        for rec in found["listed"]:
            print("  " + json.dumps(rec), flush=True)
    with open(os.path.join(a.out, a.config + ".json"), "w",
              encoding="utf-8") as fh:
        json.dump(result, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
