#!/usr/bin/env python
"""Sweep of the grouped product's tiles on the chip, kernel alone, at the
shapes the expert cells run (PR 35): what ``ops/grouped_matmul.py``
``_tiling``'s rule and the table in its docstring were read from.

    chiprun --chips 1 -- python scripts/grouped_tiling_probe.py
    python scripts/grouped_tiling_probe.py --rehearse-cpu     # toy sizes

For each shape family (an expert layer's tokens a call, its router's
width, the experts held, their widths) it draws group sizes the way the
layer's router would (top-k of seeded scores over ALL the router's
outputs, the held ones kept), then times megablox's ``gmm`` at every
candidate ``(tm, tk, tn)`` in both directions (in -> hidden as the gate
and up products, hidden -> in as the down product): ``--products``
products a jitted call over ``--weights`` distinct right-hand sides (a
layer's gate and up share rows and sizes, so the group metadata is made
once a call as in the layer), the median and the least of ``--calls``
calls, per product.  Then ``grouped_matmul`` itself, the rule's choice
beside the sweep's best, and two things the layer does NOT do, for
``PERF.md`` section 7: gate and up as one product over the two matrices
side by side, and megablox's group metadata alone.

Writes ``chiprun_out/grouped_tiling/sweep.json`` and prints one line a
candidate.  Exit 2 unless the backend is a TPU (or ``--rehearse-cpu``).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: name -> tokens a call, top_k, router outputs, experts held, in, hidden
FAMILIES = {
    "longcat_decode": (128, 12, 768, 16, 6144, 2048),
    "longcat_prefill": (256, 12, 768, 16, 6144, 2048),
    "joyai_decode": (512, 8, 256, 32, 2048, 768),
    "sdar_decode": (512, 8, 128, 128, 2048, 768),
    "sdar_prefill": (256, 8, 128, 128, 2048, 768),
    "zaya_decode": (256, 1, 16, 16, 2048, 2048),
}
TOY = {
    "toy_few_rows": (16, 4, 24, 4, 256, 128),
    "toy_full": (32, 4, 8, 8, 128, 384),
}
#: (K, N) -> lane tiles tried; the first is the tiling before PR 35
LANES = {
    (2048, 768): [(2048, 256), (2048, 768), (2048, 384), (1024, 768),
                  (512, 768)],
    (768, 2048): [(256, 1024), (768, 2048), (768, 1024), (384, 2048),
                  (768, 512)],
    (6144, 2048): [(2048, 1024), (3072, 1024), (6144, 512), (1024, 2048),
                   (6144, 256), (2048, 512)],
    (2048, 6144): [(2048, 1024), (2048, 1536), (1024, 2048), (2048, 2048),
                   (2048, 512)],
    (2048, 2048): [(2048, 1024), (1024, 2048), (2048, 512), (1024, 1024),
                   (2048, 2048)],
    (256, 128): [(128, 128), (256, 128)],
    (128, 256): [(128, 128), (128, 256)],
    (128, 384): [(128, 128), (128, 384)],
    (384, 128): [(128, 128), (384, 128)],
}
ROWS = (16, 32, 64, 128)


def routed_sizes(rng, tokens, top_k, outputs, held):
    """Group sizes of the held experts ``[0, held)`` for ``tokens`` rows
    routed top-``top_k`` over ``outputs`` seeded scores (logits of the
    spread a N(0, 0.02) router gives unit-RMS rows of 2048)."""
    import numpy as np

    logits = rng.normal(size=(tokens, outputs)) * 0.9
    chosen = np.argsort(-logits, axis=1)[:, :top_k].reshape(-1)
    return np.bincount(chosen[chosen < held], minlength=held)[:held]


def time_calls(fn, args, calls):
    import jax

    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t0)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--families", default="")
    ap.add_argument("--products", type=int, default=6)
    ap.add_argument("--weights", type=int, default=3)
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--seed", type=int, default=35)
    ap.add_argument("--out", default="chiprun_out/grouped_tiling")
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        gmm, make_group_metadata)

    from bigdl_tpu.ops.grouped_matmul import _tiling, grouped_matmul

    dev = jax.devices()[0]
    print(f"platform {dev.platform} kind {dev.device_kind} "
          f"count {jax.device_count()}", flush=True)
    if dev.platform != "tpu" and not a.rehearse_cpu:
        print("no TPU: a time from this backend is nobody's", flush=True)
        return 2
    families = TOY if a.rehearse_cpu else FAMILIES
    if a.families:
        families = {f: families[f] for f in a.families.split(",")}
    interpret = dev.platform != "tpu"
    rng = np.random.default_rng(a.seed)
    rows_out = []

    for name, (tokens, top_k, outputs, held, dim, hidden) in families.items():
        m = tokens * top_k
        sizes_np = routed_sizes(rng, tokens, top_k, outputs, held)
        sizes = jnp.asarray(sizes_np, jnp.int32)
        head = dict(family=name, m=m, groups=held, live=int(sizes_np.sum()),
                    hit=int((sizes_np > 0).sum()),
                    max_load=int(sizes_np.max()),
                    rows_per_group=tokens * top_k / outputs)
        print(json.dumps(head), flush=True)
        prefill = name.endswith("prefill")

        def record(label, tiling, fn, args, k=0, n=0):
            """Time ``fn(*args)``, ``--products`` products' worth a call."""
            rec = dict(head, k=k, n=n, tiling=tiling, label=label,
                       read_ms=head["hit"] * k * n * 2 / 819e9 * 1e3)
            try:
                t = time_calls(jax.jit(fn), args, a.calls)
                rec["ms_median"] = statistics.median(t) / a.products * 1e3
                rec["ms_min"] = min(t) / a.products * 1e3
            except Exception as e:  # noqa: BLE001 — a tiling the
                # compiler refuses is a finding of the sweep
                rec["error"] = f"{type(e).__name__}: {str(e)[:200]}"
            rows_out.append(rec)
            print(json.dumps(rec), flush=True)

        for k, n, out_dtype in ((dim, hidden, jnp.bfloat16),
                                (hidden, dim, jnp.float32)):
            key = jax.random.PRNGKey(a.seed)
            lhs = jax.random.normal(key, (m, k), jnp.bfloat16)
            rhs = [jax.random.normal(jax.random.fold_in(key, i),
                                     (held, k, n), jnp.bfloat16) * k ** -0.5
                   for i in range(a.weights)]

            def products(tiling, count=a.products):
                def fn(lhs, rhs, sizes):
                    return [gmm(lhs, rhs[j % len(rhs)], sizes,
                                preferred_element_type=out_dtype,
                                tiling=tiling, interpret=interpret)
                            for j in range(count)]
                return fn

            lanes = LANES[(k, n)][:2] if prefill else LANES[(k, n)]
            for tk, tn in lanes:
                for tm in ROWS:
                    if m % tm == 0:
                        record("sweep", [tm, tk, tn], products((tm, tk, tn)),
                               (lhs, rhs, sizes), k, n)

            def ruled(lhs, rhs, sizes):
                return [grouped_matmul(
                    lhs, rhs[j % len(rhs)], sizes,
                    preferred_element_type=out_dtype,
                    impl="pallas_interpret" if interpret else "pallas")
                    for j in range(a.products)]

            rule = _tiling(k, n)
            record("rule", list(rule), ruled, (lhs, rhs, sizes), k, n)

            if k == dim and not prefill:
                # NOT what the layer does (PERF.md section 7): gate and
                # up as ONE product over the two matrices side by side;
                # a call makes half as many, so a row reads per matrix
                both = [jnp.concatenate([rhs[i], rhs[i + 1]], axis=2)
                        for i in range(2)]
                for tk, tn in lanes[:2]:
                    record("gate_up_as_one", [rule[0], tk, tn],
                           products((rule[0], tk, tn), a.products // 2),
                           (lhs, both, sizes), k, 2 * n)
                del both

        # NOT what the layer shares (PERF.md section 7): megablox makes
        # its group metadata inside every product; alone it costs
        def metadata(tm):
            def fn(sizes):
                return [make_group_metadata(
                    group_sizes=sizes + j, m=m, tm=tm,
                    start_group=jnp.int32(0), num_nonzero_groups=held,
                    visit_empty_groups=False) for j in range(a.products)]
            return fn

        for tm in ROWS:
            if m % tm == 0:
                record("metadata_alone", [tm, 0, 0], metadata(tm), (sizes,))

    os.makedirs(a.out, exist_ok=True)
    with open(os.path.join(a.out, "sweep.json"), "w") as f:
        json.dump({"device": dev.device_kind, "jax": jax.__version__,
                   "products": a.products, "calls": a.calls,
                   "seed": a.seed, "rows": rows_out}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
