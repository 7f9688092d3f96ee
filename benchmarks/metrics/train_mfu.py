"""Kernels: the operations the forward and backward passes require for
the images trained in the window, over what the cell's chips could do
at their bf16 peak in that time."""

from benchmarks.lib import flops


def read(run):
    rate = run.e2e.get("train_samples_per_s")
    if rate is None:
        return None
    per_image = flops.resnet50_train_flops_per_image(
        int(run.config["image_size"]), int(run.config["num_classes"]))
    return 100.0 * per_image * rate / (
        run.chips * run.peaks["bf16_flops_per_s"])
