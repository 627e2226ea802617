"""Share of its roofline that the ell_spmm Pallas kernel reaches: the least time
the chip could take for the calls in the traced window, over their measured
time, in percent (bench/lib/work.py)."""
from lib.work import roofline_share


def read(layer):
    return roofline_share(layer, "ell_spmm")
