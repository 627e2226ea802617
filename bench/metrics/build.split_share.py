"""Share of the device's busy time in a traced build spent in the split
programs (split_node, split_nodes_batch), in percent."""


def read(layer):
    tr = layer.get("trace")
    if tr is None or tr["busy_s"] <= 0:
        return None
    return 100.0 * tr["program_s"]["split_node"] / tr["busy_s"]
