from repro.kernels.segment_spmm.ops import segment_spmm, segment_spmm_routed

__all__ = ["segment_spmm", "segment_spmm_routed"]
