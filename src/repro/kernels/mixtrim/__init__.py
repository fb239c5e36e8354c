from repro.kernels.mixtrim.ops import mixtrim
from repro.kernels.mixtrim.ref import mixtrim_ref

__all__ = ["mixtrim", "mixtrim_ref"]
