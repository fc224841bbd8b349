"""Compile the Pallas kernels of the main path for a described TPU v5e.

No chip is attached: the TPU compiler compiles for a topology it is only
told about, so these tests catch what interpret mode cannot — block shapes
off the (8, 128) tiling, primitives Mosaic cannot lower, more VMEM than a
kernel may use.  Each case builds its plan through ``repro.api.sparse`` on
the Pallas backend and compiles the registered kernel with
``interpret=False`` at a size where the dense operand cannot sit in VMEM.

The topology is described inside a fixture (never at import): only one
process may load the TPU library, and pytest-xdist workers import every
test file.  Each compile also shows the stable ``name=`` of the kernels it
holds (``repro_<kernel>``) as HLO instruction names, which is how a device
trace names them.
"""
import re
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import api
from repro.core import csr_from_coo, rmat
from repro.core.registry import resolve


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    # a compile for a described chip cannot be read back from the
    # persistent cache here; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


@pytest.fixture(scope="module")
def graph():
    """R-MAT scale 16: x at N=128 is 32 MiB of f32, four times VMEM."""
    return api.sparse(rmat(16, 16, seed=1), backend="pallas")


def _spec(a, sharding):
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)


def _compile(fn, args, sharding, names=()):
    """Compile ``fn`` for ``sharding``'s device; each of ``names`` must name
    a custom call of the compiled program."""
    specs = jax.tree_util.tree_map(lambda a: _spec(a, sharding), args)
    compiled = jax.jit(fn).lower(*specs).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for name in names:
        assert re.search(rf"%{name}(\.\d+)? = .*tpu_custom_call", text), name
    return compiled


def _split_opts(opts):
    arrays = {k: v for k, v in opts.items() if hasattr(v, "shape")}
    return arrays, {k: v for k, v in opts.items() if k not in arrays}


def _compile_matmul(p, impl, x, sharding, name, **kw):
    entry = p.entry(impl)
    arrays, statics = _split_opts(p.kernel_opts(entry))

    def run(sub, arrays, x):
        return entry.fn(sub, x, interpret=False, **statics, **arrays, **kw)

    return _compile(run, (p.substrate(entry.substrate), arrays, x), sharding,
                    (name,))


@pytest.mark.parametrize("impl,n", [("nb_pr", 128), ("rs_pr", 128),
                                    ("nb_pr", 1), ("nb_pr", 256)])
def test_spmm_compiles(no_cache, one_chip, graph, impl, n):
    k = graph.shape[1]
    x = jax.ShapeDtypeStruct((k, n) if n > 1 else (k,), jnp.float32)
    assert graph.plan.entry(impl).backend == "pallas"
    _compile_matmul(graph.plan, impl, x, one_chip,
                    "repro_vsr_fused" if n > 1 else "repro_spmv_fused")


@pytest.mark.parametrize("n", [128, 1])
def test_quantized_nb_kernels_compile(no_cache, one_chip, graph, n):
    """An int8 value stream through the nnz-balanced kernels: the stream is
    quantized in graph and each kernel rescales it in register from the
    per-tile scales it takes as a fourth scalar-prefetch operand."""
    k = graph.shape[1]
    x = jax.ShapeDtypeStruct((k, n) if n > 1 else (k,), jnp.float32)
    _compile_matmul(graph.plan, "nb_pr", x, one_chip,
                    "repro_vsr_fused" if n > 1 else "repro_spmv_fused",
                    quant="int8")


@pytest.mark.parametrize("tile,wb", [(1024, 128), (512, 128), (128, 512)])
def test_wide_spmm_fits_vmem_at_any_geometry(no_cache, one_chip, graph,
                                             tile, wb):
    """4,096 columns under the largest autotuner candidates and under an
    output block taller than the tile (a geometry a thresholds file may
    carry): ``gather_width`` fits the taller of the gather slots and the
    output blocks, so the kernel's VMEM stays within its limit."""
    from repro.core import TileGeometry, plan
    from repro.kernels.vsr import GATHER_BYTES, gather_width
    p = plan(graph.plan.csr, backend="pallas",
             geometry=TileGeometry(tile=tile, wb=wb))
    w = gather_width(tile, 4096, wb)
    assert 2 * 4 * w * (tile + wb) <= 2 * GATHER_BYTES
    x = jax.ShapeDtypeStruct((graph.shape[1], 4096), jnp.float32)
    _compile_matmul(p, "nb_pr", x, one_chip, "repro_vsr_fused")


def test_bsr_compiles(no_cache, one_chip):
    p = api.sparse(rmat(10, 4, seed=1), backend="bsr").plan
    x = jax.ShapeDtypeStruct((p.csr.shape[1], 128), jnp.float32)
    _compile_matmul(p, "nb_pr", x, one_chip, "repro_bsr")


def test_kernel_names_are_unique():
    """Every ``pallas_call`` of ``repro.kernels`` passes a ``name=`` of its
    own, so a trace can tell the kernels apart."""
    src = Path(api.__file__).parent / "kernels"
    calls, names = 0, []
    for path in sorted(src.glob("*.py")):
        text = path.read_text()
        calls += text.count("pl.pallas_call(")
        names += re.findall(r'name="(repro_\w+)"', text)
    assert calls == len(names) == 8
    assert len(set(names)) == len(names), names


def _chain_operands(p, logical):
    entry = p.entry(logical)
    arrays, statics = _split_opts(p.kernel_opts(entry))
    bal = p.substrate("balanced")
    return entry, arrays, statics, bal


def test_sddmm_compiles(no_cache, one_chip, graph):
    m, k = graph.shape
    entry, _, _, bal = _chain_operands(graph.plan, "sddmm")
    a = jax.ShapeDtypeStruct((m, 64), jnp.float32)
    b = jax.ShapeDtypeStruct((k, 64), jnp.float32)

    def run(rows, cols, a, b):
        return entry.fn(rows, cols, a, b, interpret=False, shape=(m, k))

    _compile(run, (bal.rows, bal.cols, a, b), one_chip, ("repro_sddmm",))


def test_row_gather_remainder_compiles(no_cache, one_chip):
    """The SDDMM at a tile of 131 nonzeros, which no ``GATHER_UNROLL``
    of 2 to 64 divides: its gathers issue the last copies from the issue
    loop's static tail.  Its index blocks are SMEM and its output block
    spans the whole ``(1, T)``, so any ``T`` is a legal tile."""
    from repro.core import csr_to_balanced
    from repro.kernels.fused_chain import sddmm_pallas
    from repro.kernels.vsr import GATHER_UNROLL
    m, k, t = 4096, 4096, 131
    assert t % GATHER_UNROLL
    bal = csr_to_balanced(rmat(12, 8, seed=2), tile=t)
    a = jax.ShapeDtypeStruct((m, 1024), jnp.float32)
    b = jax.ShapeDtypeStruct((k, 1024), jnp.float32)

    def run(rows, cols, a, b):
        return sddmm_pallas(rows, cols, a, b, interpret=False, shape=(m, k))

    _compile(run, (bal.rows, bal.cols, a, b), one_chip, ("repro_sddmm",))


def test_fused_chain_compiles(no_cache, one_chip, graph):
    m, k = graph.shape
    entry, arrays, statics, bal = _chain_operands(graph.plan, "chain")
    a = jax.ShapeDtypeStruct((m, 64), jnp.float32)
    b = jax.ShapeDtypeStruct((k, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((k, 64), jnp.float32)

    def run(rows, cols, a, b, x, arrays):
        return entry.fn(rows, cols, a, b, x, interpret=False, shape=(m, k),
                        transform="softmax", alpha=0.125, **statics,
                        **arrays)

    _compile(run, (bal.rows, bal.cols, a, b, x, arrays), one_chip,
             ("repro_chain_stats", "repro_chain_apply"))


def test_block_sparse_attention_compiles(no_cache, one_chip):
    seq, d = 4096, 128
    p = api.attention_plan(api.sliding_window(seq, 2, block=64),
                           backend="pallas", cache=False)
    entry, arrays, statics, bal = _chain_operands(p, "attn_chain")
    q = jax.ShapeDtypeStruct((seq, d), jnp.float32)

    def run(rows, cols, q, k, bias, v, arrays):
        return entry.fn(rows, cols, q, k, bias, v, interpret=False,
                        shape=(seq, seq), scale=d ** -0.5, **statics,
                        **arrays)

    bias = jax.ShapeDtypeStruct(bal.rows.shape, jnp.float32)
    _compile(run, (bal.rows, bal.cols, q, q, bias, q, arrays), one_chip,
             ("repro_attn_stats", "repro_attn_apply"))


def test_sparse_ffn_spmm_compiles(no_cache, one_chip):
    """One pruned FFN weight (d_ff 8192 x d_model 2048 at density 0.15)
    against 2048 tokens, the way ``execute_pattern`` binds it."""
    m, k, tile, tokens = 8192, 2048, 512, 2048
    rng = np.random.default_rng(0)
    flat = np.sort(rng.choice(m * k, size=int(m * k * 0.15), replace=False))
    csr = csr_from_coo(flat // k, flat % k,
                       np.ones(len(flat), np.float32), (m, k))
    p = api.sparse(csr, backend="pallas", tile=tile).plan
    x = jax.ShapeDtypeStruct((k, tokens), jnp.bfloat16)
    _compile_matmul(p, "nb_pr", x, one_chip, "repro_vsr_fused")


def test_execute_pattern_prep_is_traceable(no_cache, one_chip):
    """The sparse-FFN layers scan per-layer patterns: their visit schedules
    arrive traced, as kernel operands, and still compile to the kernel."""
    m, k = 512, 256
    csr = rmat(9, 8, seed=2, m=m, k=k)
    bal = api.sparse(csr, backend="pallas").plan.substrate("balanced")
    opts = resolve("nb_pr", "pallas").prep(bal)
    arrays, statics = _split_opts(opts)
    x = jax.ShapeDtypeStruct((k, 128), jnp.float32)

    def run(rows, cols, vals, x, arrays):
        return api.pattern_matmul(rows, cols, vals, (m, k), x,
                                  backend="pallas", interpret=False,
                                  prep=dict(arrays, **statics))

    _compile(run, (bal.rows, bal.cols, bal.vals.reshape(-1), x, arrays),
             one_chip, ("repro_vsr_fused",))


@pytest.fixture(scope="module")
def phi3_ffn():
    """One pruned Phi-3-mini FFN weight (d_ff 8192 x d_model 3072 at
    density 0.1, tiles of 512) and its prep, transpose included."""
    m, k, tile = 8192, 3072, 512
    rng = np.random.default_rng(0)
    flat = np.sort(rng.choice(m * k, size=int(m * k * 0.1), replace=False))
    n_tiles = -(-len(flat) // tile)
    pad = n_tiles * tile - len(flat)
    rows = np.concatenate([flat // k, np.full(pad, m)]).astype(np.int32)
    cols = np.concatenate([flat % k, np.zeros(pad)]).astype(np.int32)
    rows, cols = rows.reshape(n_tiles, tile), cols.reshape(n_tiles, tile)
    return rows, cols, (m, k), api.pattern_prep(rows, cols, (m, k))


@pytest.mark.parametrize("tokens,blocks", [(1024, 1), (4096, 4)])
def test_sparse_ffn_spmm_compiles_at_full_width(no_cache, one_chip, phi3_ffn,
                                                tokens, blocks):
    """The pruned FFN's SpMM at 1,024 tokens gathers each row once, in one
    1,024-wide block of ``(rows, 1, w)`` unit rows; at 4,096 tokens (the
    model's context) in four.  Two ``(512, 1024)`` f32 gather slots take
    the 4 MiB ``GATHER_BYTES`` of VMEM, which the compile checks."""
    from repro.kernels.vsr import gather_width
    rows, cols, shape, prep = phi3_ffn
    assert -(-tokens // gather_width(rows.shape[1], tokens)) == blocks
    arrays, statics = _split_opts(prep)
    vals = jax.ShapeDtypeStruct((rows.size,), jnp.float32)
    x = jax.ShapeDtypeStruct((shape[1], tokens), jnp.float32)

    def run(vals, x, rows, cols, arrays):
        return api.pattern_matmul(rows, cols, vals, shape, x,
                                  backend="pallas", interpret=False,
                                  prep=dict(arrays, **statics))

    _compile(run, (vals, x, rows, cols, arrays), one_chip,
             ("repro_vsr_fused",))


@pytest.mark.parametrize("tokens", [1024, 4096])
def test_sparse_ffn_backward_compiles(no_cache, one_chip, phi3_ffn, tokens):
    """The gradient of one pruned FFN matrix at Phi-3-mini widths: the
    value gradient on ``repro_sddmm`` (feature-blocked at 4096 tokens) and
    the input gradient on ``repro_vsr_fused`` over the transposed pattern,
    with no ``(nnz, tokens)`` intermediate — which could not fit the chip."""
    rows, cols, shape, prep = phi3_ffn
    arrays, statics = _split_opts(prep)
    vals = jax.ShapeDtypeStruct((rows.size,), jnp.float32)
    x = jax.ShapeDtypeStruct((shape[1], tokens), jnp.float32)

    def loss(vals, x, rows, cols, arrays):
        y = api.pattern_matmul(rows, cols, vals, shape, x, backend="pallas",
                               interpret=False, prep=dict(arrays, **statics))
        return jnp.sum(y * y)

    compiled = _compile(jax.grad(loss, argnums=(0, 1)),
                        (vals, x, rows, cols, arrays), one_chip,
                        ("repro_vsr_fused", "repro_sddmm"))
    text = compiled.as_text()
    assert len(re.findall(r"%repro_vsr_fused(\.\d+)? = ", text)) == 2
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < rows.size * tokens * 4
