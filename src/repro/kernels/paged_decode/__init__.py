from repro.kernels.paged_decode.ops import paged_decode

__all__ = ["paged_decode"]
