"""Executions of the jitted search step per query row answered, in the traced
window."""


def read(layer):
    tr = layer.get("trace")
    rows = layer.get("rows_traced", 0)
    if tr is None or not rows:
        return None
    return tr["program_calls"]["_beam_search"] / rows
