"""Multi-device layer: pencil FFTs, compressed collectives, straggler
mitigation, pipeline parallelism.

Everything here speaks shard_map + named mesh axes, taken from
:mod:`repro.dist._compat`.
"""
from . import compression, pencil, pipeline, straggler  # noqa: F401
from ._compat import all_to_all, make_mesh, shard_map  # noqa: F401
from .compression import (all_to_all_compressed, psum_compressed,  # noqa: F401
                          wire_bytes)
from .pencil import (pfft1d, pfft2, pfft2_hierarchical, pfft3,  # noqa: F401
                     pirfft2, prfft2, pack_half_spectrum,
                     unpack_half_spectrum)
from .pipeline import pipelined_apply  # noqa: F401
from .straggler import rebalance, should_eject  # noqa: F401
