"""Serving host loop: share of the drafts the window's decode steps
verified that were accepted (the step then yields two tokens), from the
``draft_accepted`` and ``draft_verified`` attributes of the
``serve.decode_step`` spans (``bigdl_tpu/serving/spans.py``).

With weights drawn from a seed the prediction layer agrees with the
main model about once in a vocabulary: the cell reads near 0 %, where a
trained checkpoint accepts most drafts.  It is reported as measured."""


def read(run):
    steps = [s["attrs"] for s in run.spans
             if s["name"] == "serve.decode_step"
             and "draft_verified" in s["attrs"]]
    verified = sum(a["draft_verified"] for a in steps)
    if not verified:
        return None
    return 100.0 * sum(a["draft_accepted"] for a in steps) / verified
