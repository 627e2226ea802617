"""Mean time of one offline-engine call inside ServingEngine: the engine_call
span of the Profiler the harness hands the engine, in ms."""


def read(layer):
    span = layer.get("spans", {}).get("engine_call")
    if not span or not span["count"]:
        return None
    return float(1e3 * span["seconds"] / span["count"])
