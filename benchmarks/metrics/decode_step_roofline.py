"""Kernels: the least time the chip could take for the decode steps of
the traced window, over the device time they took.

The least time of one step is the larger of its operations over the
chip's peak rate and its bytes over the peak bandwidth
(``lib/flops.py``): every weight matrix once, and the K and V pages that
the step's page-table bucket names, for the slots the engine was built
with.  The buckets are read from the engine's ``serve.decode_step``
spans, so a window that moves between buckets is weighted as it ran."""

from benchmarks.lib import flops, xplane


def read(run):
    device_ms = xplane.program_ms_per_call(run.trace, "step")
    buckets = [s["attrs"].get("bucket") for s in run.spans
               if s["name"] == "serve.decode_step"]
    buckets = [int(b) for b in buckets if b is not None]
    if device_ms is None or not buckets:
        return None
    sizes, c = run.extra["sizes"], run.counters
    least = 0.0
    for pages in buckets:
        least += flops.roofline_seconds(
            flops.gpt2_decode_step_flops(
                sizes["n_layer"], sizes["dim"], sizes["vocab"], c["batch"],
                pages * c["page_size"], sizes["mlp_ratio"]),
            flops.gpt2_decode_step_bytes(
                sizes["n_layer"], sizes["dim"], sizes["vocab"], c["batch"],
                pages, c["page_size"], c["weight_itemsize"],
                c["kv_itemsize"], sizes["mlp_ratio"]),
            run.peaks)
    return 100.0 * (1e3 * least / len(buckets)) / device_ms
