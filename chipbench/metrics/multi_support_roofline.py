"""Kernels, mining: percent of its HBM roofline the multi-prefix support
sweep reached over the traced mines: the least bytes of its calls at the
chip's peak HBM bandwidth, over their device time.  No compute bound is
claimed: v5e publishes no peak for VPU integer word operations."""
from cost import multi_support


def read(r):
    return r.hbm_roofline(multi_support.MATCH,
                          lambda name: multi_support.event_bytes(name,
                                                                 r.config))
