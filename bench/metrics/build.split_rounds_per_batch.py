"""Split rounds per 256-document batch in a traced build: executions of the
split programs (split_node, split_nodes_batch; one a round of the split
cascade) over the batches the build inserts."""


def read(layer):
    tr = layer.get("trace")
    if tr is None or not layer.get("batches_traced"):
        return None
    return tr["program_calls"]["split_node"] / layer["batches_traced"]
