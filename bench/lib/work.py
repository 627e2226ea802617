"""Operations and bytes that one call of each Pallas kernel needs, from the
sizes of the work it is given, and the least time a chip could take for it.

Sizes are the logical ones: query or document rows, centres, vocabulary
width, ELL slots per row, k. The padding a kernel wrapper adds (rows and
centres to 128, the vocabulary to a multiple of 128) is not work the
algorithm needs, so it counts against the share. Floats are f32, ids i32.
"""
from __future__ import annotations

F32 = 4
I32 = 4


def nn_assign(rows: int, centres: int, dim: int) -> dict:
    """Nearest centre of each row: ‖x‖² − 2x·c + ‖c‖², then an argmin."""
    flops = 2 * rows * centres * dim + 2 * (rows + centres) * dim
    bytes_ = F32 * (rows * dim + centres * dim + centres) + (F32 + I32) * rows
    return {"flops": flops, "bytes": bytes_}


def nn_topk(rows: int, centres: int, dim: int, k: int) -> dict:
    """The k nearest centres of each row: the distances of ``nn_assign`` and
    a k-selection."""
    flops = 2 * rows * centres * dim + 2 * (rows + centres) * dim
    bytes_ = F32 * (rows * dim + centres * dim + centres) + (F32 + I32) * rows * k
    return {"flops": flops, "bytes": bytes_}


def ell_spmm(rows: int, nnz: int, centres: int, dim: int) -> dict:
    """Scores S = X·Cᵀ of ELL rows (``nnz`` value and column slots each)
    against dense centres: one multiply-add per stored slot and centre."""
    flops = 2 * rows * nnz * centres
    bytes_ = (F32 + I32) * rows * nnz + F32 * centres * dim + F32 * rows * centres
    return {"flops": flops, "bytes": bytes_}


KERNELS = {"nn_assign": nn_assign, "nn_topk": nn_topk, "ell_spmm": ell_spmm}


def roofline_seconds(work: dict, peak: dict) -> tuple[float, str]:
    """(least seconds, "compute" or "hbm"): the larger of operations over the
    peak rate and bytes over the peak bandwidth, and which one bounds it."""
    t_c = work["flops"] / peak["flops_per_s"]
    t_m = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "hbm")


def roofline_share(layer: dict, kernel: str):
    """Percent of the least time the chip could take over the kernel's
    measured time, in a traced run: the work of one call (from the sizes in
    ``layer["kernel_work"]``) times the calls in the trace, against the
    summed duration of those calls. None where the trace holds no call."""
    tr = layer.get("trace")
    sizes = layer.get("kernel_work", {}).get(kernel)
    if tr is None or sizes is None or "peak" not in layer:
        return None
    calls, seconds = tr["kernel_calls"].get(kernel, 0), tr["kernel_s"].get(kernel, 0.0)
    if calls == 0 or seconds <= 0:
        return None
    least, _ = roofline_seconds(KERNELS[kernel](**sizes), layer["peak"])
    return 100.0 * calls * least / seconds
