"""Mean time a request waited in ServingEngine's queue, from admission to the
moment its batch was popped: the engine_queue records of the Profiler the
harness hands the engine in the measured window, in ms."""


def read(layer):
    span = layer.get("spans", {}).get("engine_queue")
    if not span or not span["count"]:
        return None
    return float(1e3 * span["seconds"] / span["count"])
