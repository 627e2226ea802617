"""Query rows per batch the engine dispatched: ServingEngine.stats()
batch_occupancy × row_budget, over the window."""


def read(layer):
    stats = layer.get("stats")
    if not stats or not stats.get("n_batches"):
        return None
    return float(stats["batch_occupancy"] * layer["row_budget"])
