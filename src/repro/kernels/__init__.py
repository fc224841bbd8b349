"""Pallas TPU kernels for the paper's SpMV/SpMM space (+ ref oracles).

Importing this package self-registers the "pallas" and "bsr" backends into
``repro.core.registry`` (each kernel module registers its own entries); the
registry lazy-imports it on first resolve of a non-XLA backend.
"""
from . import attention as _attention  # registers attn_chain under "pallas"
from . import bsr as _bsr        # registers the "bsr" backend
from . import csc as _csc        # registers rs_* under "pallas"
from . import fused_chain as _fused_chain  # registers sddmm/chain "pallas"
from . import vsr as _vsr        # registers nb_* under "pallas"
from .attention import attn_chain_pallas, attn_stats_pallas
from .fused_chain import (CHAIN_TRANSFORMS, chain_pallas, chain_stats_pallas,
                          sddmm_pallas)
from .ops import spmm, spmm_bsr, spmm_csc, use_pallas_default
from .spmv import spmv_vsr_fused
from .tune import (ATTN_NEVER, CHAIN_NEVER, DEFAULT_CANDIDATES, OVERLAP_NEVER,
                   QUANT_NEVER, autotune_attention, autotune_chain,
                   autotune_geometry, autotune_overlap, autotune_quant,
                   measure_attention, measure_chain, measure_geometry,
                   measure_overlap, measure_quant, modeled_traffic_attention,
                   modeled_traffic_chain)
from .vsr import plan_visits, spmm_vsr_fused
