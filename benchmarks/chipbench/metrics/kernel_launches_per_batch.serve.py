"""Launches of the local compute backend per batch of the serving window,
dealer and online side together: the delta of the always-on registry
counter ``trident_kernel_launches_total`` over the window, over its
batches."""


def read(run):
    if run["driver"] != "serve" or not run["batches"]:
        return None
    return run["kernel_launches"] / run["batches"]
