"""The plain model's forward operations per sample, times the
traced window's predictions per second, over the chip's int8 peak
(``peaks.json``): the whole secure batch's share of the chip's peak."""


def read(run):
    if run["driver"] != "serve" or not run["samples"]:
        return None
    rate = run["samples"] / run["window_s"]
    return 100.0 * run["flops_per_sample"] * rate / run["peak_ops_per_s"]
