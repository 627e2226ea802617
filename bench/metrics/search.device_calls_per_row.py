"""Executions of the jitted search step per query row answered, in the traced
window: the program the driver names in ``layer["search_program"]`` (the
route's descent: ``_beam_search``, or ``_chunk_candidates_jit`` for a Random
Indexing tree), ``_beam_search`` where it names none."""


def read(layer):
    tr = layer.get("trace")
    rows = layer.get("rows_traced", 0)
    if tr is None or not rows:
        return None
    return tr["program_calls"][layer.get("search_program", "_beam_search")] / rows
