"""Kernels: the pages the decode attention kernels' page stream copied
for every copy descriptor it started, averaged over the window's
decode steps: ``attn_rows_copied`` / the page size / ``attn_copies`` of
each ``serve.decode_step`` span (``bigdl_tpu/serving/spans.py``; the
kernel's own arithmetic over the page tables the step was handed,
``ops/decode_attention.py`` ``stream_copies``).

A group of 8 table entries that name neighbouring pages is ONE
descriptor, any other group one a page: a scattered table reads 1.0, a
table of runs, as ``serving/cache.py``'s allocator hands pages out,
8.0.  ``None`` where no span carries ``attn_copies``: a program from
before the counter, or a model whose attention streams no pages."""


def read(run):
    page = run.counters.get("page_size")
    steps = [s["attrs"] for s in run.spans
             if s["name"] == "serve.decode_step"
             and s["attrs"].get("attn_copies")]
    if not steps or not page:
        return None
    return sum(a["attn_rows_copied"] / page / a["attn_copies"]
               for a in steps) / len(steps)
