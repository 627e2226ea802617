"""Insertion waves per 256-document batch in a traced build: executions of the
_insert_wave program over the batches the build inserts."""


def read(layer):
    tr = layer.get("trace")
    if tr is None or not layer.get("batches_traced"):
        return None
    return tr["program_calls"]["_insert_wave"] / layer["batches_traced"]
