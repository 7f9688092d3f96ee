#!/usr/bin/env python
"""The pick at a decode step's end on the chip, at the shapes and
dtypes of the six serving cells' logits (PR 40): what
``serving/engine.py`` ``sample_step`` costs with its draw under a
``cond``, beside what it cost before.

    chiprun --chips 1 -- python scripts/pick_probe.py
    python scripts/pick_probe.py --rehearse-cpu               # toy sizes

**Alone** (``where: "alone"``): the logits ``(rows, vocabulary)`` lie in
memory, ``--arrays`` of them in one jitted call (so that the chip sets
the pace, not the host), ``--inner`` calls in flight, the median and
the least of ``--calls`` readings, per pick:

``argmax``            ``engine.pick_greedy``: ``jnp.argmax``
``old_sample_step``   ``sample_step`` as it was, no slot sampling: the
                      argmax AND a categorical draw over every logit
``sample_step``       the new one, no slot sampling (the greedy arm)
``*.draw``            both with ONE slot sampling (the new one's other
                      arm is the old formula: same tokens, same cost)

The temperatures are an argument of the program, as in the engine: a
constant would let the compiler drop the draw.

**Behind the head's product** (``where: "head"``): the same over
``h @ W.T`` with the cell's hidden width, ``--arrays`` rows of ``h``
against one head, product and pick timed together, because the
compiler fuses across the two: a ``jnp.argmax`` that is the logits'
only reader goes INTO the product and no logit is written
(``head+argmax``, what a drafting or block model's pick is; it then
compares the sums before they are rounded to the logits' dtype, so
``differ_from_argmax_of_logits`` counts its other choices among tied
logits).

Beside the time: alone, the logits' bytes over it in GB/s and as a
share of the chip's memory rate.  Every pick's tokens are compared
with ``jnp.argmax``'s (a draw's with the old formula's) before it is
timed.  Writes ``chiprun_out/pick_probe/probe.json`` and prints one
line a timing.  Exit 2 unless the backend is a TPU (or
``--rehearse-cpu``).  Nothing a cell runs imports this file.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: cell -> rows of logits a step, vocabulary, hidden width
#: (``benchmarks/configs``: ``max_batch`` x positions a slot,
#: ``vocab_size``, ``hidden_size``), and whether the engine's
#: ``sample_step`` picks ("step") or the model, with ``pick_greedy``
CELLS = {
    "zaya1_cca_long_gen": (256, 262272, 2048, "step"),
    "falcon_h1_ssm_long_gen": (128, 261120, 5120, "step"),
    "joyai_flash_draft_gen": (512, 129280, 2048, "pick"),
    "joyai_flash_draft_gen.mtp": (256, 129280, 2048, "pick"),
    "sdar_moe_block_gen": (512, 151936, 2048, "pick"),
    "longcat_flash_long_gen": (128, 16384, 6144, "step"),
    "gpt2xl_gen_heavy": (12, 50257, 1600, "step"),
    "prefill_first_token": (1, 262272, 2048, "pick"),
}
TOY = {"toy_step": (12, 1411, 64, "step"),
       "toy_pick": (40, 1152, 64, "pick")}


def old_sample_step(logits, temps, active, key):
    import jax
    import jax.numpy as jnp

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sampled = jax.random.categorical(
        key, logits / jnp.maximum(temps, 1e-6)[:, None],
        axis=-1).astype(jnp.int32)
    nxt = jnp.where(temps > 0.0, sampled, greedy)
    return jnp.where(active, nxt, 0)


def time_calls(fn, args, inner, calls):
    """Milliseconds a call of the jitted ``fn``, ``inner`` in flight."""
    import jax

    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready([fn(*args) for _ in range(inner)])
        out.append((time.perf_counter() - t0) * 1e3 / inner)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--cells", default="")
    ap.add_argument("--where", choices=("alone", "head"),
                    help="only the picks alone, or only those behind "
                    "the head's product")
    ap.add_argument("--arrays", type=int, default=8)
    ap.add_argument("--inner", type=int, default=2)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=40)
    ap.add_argument("--out", default="chiprun_out/pick_probe")
    a = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib.peaks import peaks_for
    from bigdl_tpu.serving.engine import pick_greedy, sample_step

    dev = jax.devices()[0]
    print(f"platform {dev.platform} kind {dev.device_kind} count "
          f"{jax.device_count()}", flush=True)
    if dev.platform != "tpu" and not a.rehearse_cpu:
        print("no TPU: a time from this backend is nobody's", flush=True)
        return 2
    rate = peaks_for(dev.device_kind)["hbm_bytes_per_s"] \
        if dev.platform == "tpu" else None
    cells = TOY if a.rehearse_cpu else CELLS
    if a.cells:
        cells = {c: cells[c] for c in a.cells.split(",")}
    arrays, inner, calls = (2, 1, 2) if a.rehearse_cpu \
        else (a.arrays, a.inner, a.calls)
    dt = jnp.bfloat16
    results = []

    for name, (rows, vocab, hidden, kind) in cells.items():
        key = jax.random.PRNGKey(a.seed)
        # a vocabulary's logits: a normal spread, as seeded weights give
        xs = [jax.random.normal(jax.random.fold_in(key, i), (rows, vocab),
                                jnp.float32).astype(dt)
              for i in range(arrays)]
        # what a finite draw never holds: a NaN behind an infinity, a
        # row of -inf alone, a tie across tiles
        xs[0] = xs[0].at[0, 40].set(jnp.inf).at[0, vocab // 2].set(jnp.nan)
        if rows > 2:
            xs[0] = xs[0].at[1].set(-jnp.inf).at[2, 7].set(60.0) \
                .at[2, vocab - 1].set(60.0)
        hs = [jax.random.normal(jax.random.fold_in(key, 100 + i),
                                (rows, 1, hidden), jnp.float32).astype(dt)
              for i in range(arrays)]
        w = (jax.random.normal(jax.random.fold_in(key, 99), (vocab, hidden),
                               jnp.float32) * 0.02).astype(dt)
        active = jnp.ones((rows,), bool)
        greedy_t = jnp.zeros((rows,), jnp.float32)
        one_t = greedy_t.at[rows // 2].set(0.8)
        keys = list(jax.random.split(jax.random.key(a.seed), arrays))

        def head(h, w):
            return jnp.matmul(h, w.T)[:, 0, :]

        def each(pick):
            return lambda temps, *xs: [pick(x) for x in xs]

        def stepped(step):
            return lambda temps, *xs: [step(x, temps, active, k)
                                       for x, k in zip(xs, keys)]

        def behind(fn):
            """``fn`` over the head's product of each ``h``."""
            return lambda temps, w, *hs: fn(
                temps, *[head(h, w) for h in hs])

        alone = [("argmax", each(pick_greedy))]
        after = [("head+argmax", behind(each(pick_greedy)))]
        if kind == "step":
            for label, step in (("old_sample_step", old_sample_step),
                                ("sample_step", sample_step)):
                alone += [(label, stepped(step)),
                          (label + ".draw", stepped(step))]
                after += [("head+" + label, behind(stepped(step))),
                          ("head+" + label + ".draw",
                           behind(stepped(step)))]
        cands = [("alone", *c, xs) for c in alone if a.where != "head"]
        cands += [("head", *c, (w, *hs)) for c in after
                  if a.where != "alone"]

        refs = {}
        for where, label, fn, args in cands:
            temps = one_t if label.endswith(".draw") else greedy_t
            # a candidate a compile, by design
            jitted = jax.jit(fn)  # graftlint: disable=JX003
            got = jitted(temps, *args)
            # a draw is held to the old formula's, bit for bit
            ref = stepped(old_sample_step) \
                if label.endswith(".draw") else each(pick_greedy)
            if where == "head":
                ref = behind(ref)
            # one reference a cell for every candidate that shares it
            shared = (where, label.endswith(".draw"))
            if shared not in refs:
                refs[shared] = [np.asarray(r)
                                for r in ref(temps, *args)]
            differ = 0
            for g, r in zip(got, refs[shared]):
                differ += int((np.asarray(g) != r).sum())
            # an argmax INSIDE the product compares the sums before
            # they are rounded to the logits' dtype: where two
            # logits tie it may pick another of them
            assert not differ or label == "head+argmax", (name, label)
            times = [t / arrays for t in time_calls(
                jitted, (temps, *args), inner, calls)]
            med, least = statistics.median(times), min(times)
            nbytes = rows * vocab * jnp.dtype(dt).itemsize
            rec = dict(cell=name, rows=rows, vocab=vocab, where=where,
                       pick=label, ms=med, ms_least=least)
            if where == "head":
                rec.update(differ_from_argmax_of_logits=differ)
            else:
                rec.update(gb_per_s=nbytes / (med * 1e-3) / 1e9,
                           memory_rate_share=rate and nbytes
                           / (med * 1e-3) / rate)
            results.append(rec)
            print(json.dumps(rec), flush=True)
        del xs, hs, w

    os.makedirs(a.out, exist_ok=True)
    with open(os.path.join(a.out, "probe.json"), "w") as f:
        json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
