"""Dealer wall seconds per batch of the window (``DealReport.wall_s``
summed by the server as ``offline_deal_s``): the offline producer's cost
per batch, overlapped with the online side."""


def read(run):
    if run["driver"] != "serve" or not run["batches"]:
        return None
    return run["dealer_s_per_batch"]
