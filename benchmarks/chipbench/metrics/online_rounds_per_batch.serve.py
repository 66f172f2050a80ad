"""Online communication rounds per batch of the window, from the server's
own transport accounting (``PartyServeStats.online_rounds``)."""


def read(run):
    if run["driver"] != "serve" or not run["batches"]:
        return None
    return run["online_rounds_per_batch"]
