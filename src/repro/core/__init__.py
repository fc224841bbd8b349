"""Core sparse library: formats, statistics, the 2x2 kernel space, and the
plan/execute dispatch subsystem (registry + builder/artifact plans + unified
VJP + topology-keyed plan cache).  The public surface is ``repro.api``."""
from .cache import (DEFAULT_CACHE, PlanCache, cached_plan, mesh_signature,
                    pattern_fingerprint, plan_key)
from .formats import (BSR, CSR, ELL, BalancedCOO, bsr_to_dense, csr_from_coo,
                      csr_from_dense, csr_to_balanced, csr_to_bsr, csr_to_ell,
                      reset_build_counts, row_ids_from_indptr)
from .plan import (PlanArtifact, PlanBuilder, PlanMeta, SparsePlan, execute,
                   execute_chain, execute_pattern, execute_sddmm, plan)
from .quant import (MAX_DYNAMIC_RANGE, QUANT_MODES, dequantize_stream,
                    int8_decode, int8_encode, quantize_stream, value_bytes)
from .registry import (LOGICAL_KERNELS, MATMUL_KERNELS, KernelEntry, available,
                       backend_scope, backends_for, default_backend, register,
                       resolve, scoped_backend)
from .rmat import rmat, rmat_suite, rmat_suite_small
from .selector import (PreparedMatrix, SelectorThresholds, TileGeometry,
                       adaptive_spmm, calibrate, default_thresholds,
                       geometry_key, load_thresholds, n_bucket,
                       save_thresholds, select_kernel, select_partition)
from .shard import (ShardSpec, ShardedSubstrate, build_sharded_substrate,
                    execute_pattern_sharded, make_shard_spec)
from .spmm import (spmm_as_n_spmv, spmm_nb_pr, spmm_nb_pr_trainable,
                   spmm_nb_sr, spmm_rs_pr, spmm_rs_sr)
from .stats import MatrixStats, matrix_stats
