"""Mean share of the engine's pool lanes that held a request, over the
window's scheduler rounds (``BIFEngine.stats()`` ``pool.occupancy``)."""


def read(run):
    hist = (run.stats or {}).get("histograms", {}).get("pool.occupancy")
    if not hist or not hist.get("count"):
        return None
    return 100.0 * float(hist["mean"])
