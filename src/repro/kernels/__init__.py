# Pallas TPU kernels for EF21-Muon's compute hot-spots:
#  - newton_schulz: blocked-matmul quintic NS orthogonalisation (Muon LMO)
#  - natural_pack: Natural-compression bit-manipulation encode
#  - topk_select: TopK's sort-free compaction of the kept entries
#  - bitpack: wire bit-packing primitives (1-bit sign planes, narrow
#    uint16/uint24 index encoding) shared by ops.py and repro.wire
# Each has a pure-jnp oracle (ref.py / bitpack.py refs) and a padded jit
# wrapper with a CPU fallback.
from .bitpack import (narrow_decode, narrow_encode, narrow_width, pack_bits,
                      unpack_bits)
from .newton_schulz import fused_ns_feasible
from .ops import (NS_COEFFS, count_ns_dispatches, natural_compress,
                  natural_decompress, newton_schulz, newton_schulz_batched,
                  resolve_use_pallas, topk_select)

__all__ = ["NS_COEFFS", "natural_compress", "natural_decompress",
           "newton_schulz", "newton_schulz_batched", "fused_ns_feasible",
           "count_ns_dispatches", "resolve_use_pallas", "topk_select",
           "pack_bits", "unpack_bits", "narrow_encode", "narrow_decode",
           "narrow_width"]
