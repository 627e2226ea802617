"""The longest batch ServingEngine ran in the measured window: the largest
engine_batch span of the Profiler the harness hands the engine, in ms."""


def read(layer):
    span = layer.get("spans", {}).get("engine_batch")
    if not span or "max_s" not in span:
        return None
    return float(1e3 * span["max_s"])
