"""Compile the serve path's Pallas kernels and decode step for a described
TPU v5e at phi3-mini-3.8b widths, without a chip attached.

Nothing here runs: the TPU compiler (installed with libtpu) lowers each
program for a device it is only told about, and refuses what the chip's
compiler would refuse — a block shape off the (8, 128) tiling, an operand
type Mosaic cannot feed the MXU, a program that does not fit HBM.  The
topology is described inside a fixture, never at import, so only the test
worker that runs this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.core import kvcache as KV
from repro.core import quant
from repro.kernels.decode_attn import kernel as da_kernel
from repro.kernels.decode_attn import ops as da_ops
from repro.kernels.int8_matmul import kernel as mm_kernel
from repro.kernels.int8_matmul import ops as mm_ops
from repro.models import model as M
from repro.models.transformer import Runtime
from repro.serve.quantize import quantize_tree

V5E_HBM_BYTES = 16 * 1024**3
SLOTS, MAX_LEN = 4, 1536          # the chip smoke's slot pool


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


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The kernels pick interpret mode from the platform, which is the CPU
    here; steer them to Mosaic for the described chip."""
    for mod in (mm_kernel, da_kernel):
        monkeypatch.setattr(mod, "resolve_interpret", lambda i: False)


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k,n", [(3072, 8192), (8192, 3072)])
def test_int8_matmul_compiles(one_chip, k, n):
    def f(x_q, x_s, w_q, w_s):
        return mm_ops.int8_matmul(x_q, x_s, quant.QuantizedLinear(w_q, w_s),
                                  out_dtype=jnp.bfloat16, interpret=False)
    compiled = jax.jit(f).lower(
        _sds(one_chip, (SLOTS, k), jnp.int8),
        _sds(one_chip, (SLOTS, 1), jnp.float32),
        _sds(one_chip, (k, n), jnp.int8),
        _sds(one_chip, (n,), jnp.float32)).compile()
    _assert_mosaic(compiled)


def _pool(one_chip, seq, g=32, d=96):
    return (_sds(one_chip, (SLOTS, seq, g, d), jnp.int8),
            _sds(one_chip, (SLOTS, seq, g, 1), jnp.float32))


def test_decode_attention_compiles(one_chip):
    cfg = registry.get("phi3-mini-3.8b")
    seq = MAX_LEN + KV.pool_headroom()
    k_q, k_s = _pool(one_chip, seq, cfg.n_kv_heads, cfg.head_dim)
    q = _sds(one_chip, (SLOTS, 1, cfg.n_heads, cfg.head_dim), jnp.bfloat16)
    length = _sds(one_chip, (SLOTS,), jnp.int32)
    compiled = jax.jit(
        lambda *a: da_ops.decode_attention(*a, interpret=False)
    ).lower(q, k_q, k_s, k_q, k_s, length).compile()
    _assert_mosaic(compiled)


@pytest.mark.parametrize("tree", [False, True], ids=["linear", "tree"])
def test_verify_attention_compiles(one_chip, tree):
    """T=8 verify windows (spec_k / spec_tree 7) over a pool with the
    matching headroom rows — a ragged last key block."""
    cfg = registry.get("phi3-mini-3.8b")
    T = 8
    seq = MAX_LEN + KV.pool_headroom(spec_tree=T - 1)
    k_q, k_s = _pool(one_chip, seq, cfg.n_kv_heads, cfg.head_dim)
    q = _sds(one_chip, (SLOTS, T, cfg.n_heads, cfg.head_dim), jnp.bfloat16)
    pos = _sds(one_chip, (SLOTS,), jnp.int32)
    if tree:
        anc = _sds(one_chip, (SLOTS, T), jnp.int32)
        fn = lambda *a: da_ops.verify_attention_tree(*a, interpret=False)
        args = (q, k_q, k_s, k_q, k_s, pos, anc)
    else:
        fn = lambda *a: da_ops.verify_attention(*a, interpret=False)
        args = (q, k_q, k_s, k_q, k_s, pos)
    _assert_mosaic(jax.jit(fn).lower(*args).compile())


@pytest.mark.parametrize("name", ["phi3-mini-3.8b", "granite-3-8b"])
@pytest.mark.parametrize("T", [1, 5], ids=["decode", "verify"])
def test_lane_dense_attention_dots_are_exact(one_chip, name, T):
    """The dense backend's QK and PV over the lane-dense pool, compiled for
    the chip: no MXU dot takes an f32 operand at default precision (the
    MXU would round it to one bf16 pass).  QK is int8 x int8; PV feeds the
    softmax weights as three bf16 parts that sum to them exactly."""
    import re
    from repro.models import attention as A
    cfg = registry.get(name)
    G, D = cfg.n_kv_heads, cfg.head_dim
    rows = _sds(one_chip, (SLOTS, MAX_LEN, G * D), jnp.int8)
    scales = _sds(one_chip, (SLOTS, MAX_LEN, G, 1), jnp.float32)
    q = _sds(one_chip, (SLOTS, T, cfg.n_heads, D), jnp.bfloat16)
    pos = _sds(one_chip, (SLOTS,), jnp.int32)
    fn = A.decode_attention_int8 if T == 1 else A.verify_attention_int8
    hlo = jax.jit(fn).lower(q, rows, scales, rows, scales,
                            pos).compile().as_text()
    types = dict(re.findall(r"%([\w.\-]+) = (\w+)\[", hlo))
    dots = [(l, [types[n] for n in re.search(
                r" (?:convolution|dot)\(%([\w.\-]+), %([\w.\-]+)\)",
                l).groups()])
            for l in hlo.splitlines()
            if re.search(r" (?:convolution|dot)\(", l)]
    assert len(dots) == 2, dots                    # QK and PV, on the MXU
    assert sorted(map(sorted, (t for _, t in dots))) == [
        ["bf16", "s8"], ["s8", "s8"]], dots
    assert not [l for l, t in dots if "f32" in t
                and "operand_precision={highest,highest}" not in l]


@pytest.mark.parametrize("backend", ["dense", "fused_int8"])
def test_phi3_decode_step_fits_hbm(one_chip, compiled_kernels, backend):
    """The serve decode step at full width: bf16 W8A8 weights from
    ``quantize_tree`` plus the 4-slot int8 pool, donated in place, fit one
    v5e's HBM; the fused backend lowers its Pallas kernels."""
    cfg = registry.get("phi3-mini-3.8b")
    params = jax.eval_shape(
        lambda: quantize_tree(M.init_params(jax.random.key(0), cfg,
                                            jnp.bfloat16)))
    state = jax.eval_shape(lambda: M.init_decode_state(cfg, SLOTS, MAX_LEN))
    place = lambda t: jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype), t)
    rt = Runtime(backend=backend)
    compiled = jax.jit(
        lambda p, s, t: M.decode_step(p, cfg, s, t, rt), donate_argnums=(1,)
    ).lower(place(params), place(state),
            _sds(one_chip, (SLOTS,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.alias_size_in_bytes > 0          # the pool updates in place
    assert total < V5E_HBM_BYTES, mem
    if backend == "fused_int8":
        _assert_mosaic(compiled)


@pytest.mark.parametrize("name,slots", [
    pytest.param("phi3-mini-3.8b", 1, id="1"),
    pytest.param("phi3-mini-3.8b", SLOTS, id=str(SLOTS)),
    pytest.param("granite-3-8b", 1, id="granite-1"),
    pytest.param("granite-3-8b", SLOTS, id=f"granite-{SLOTS}")])
def test_phi3_decode_step_keeps_pool_layout(one_chip, name, slots):
    """The decode step appends into the lane-dense int8 pool
    ([L, B, S, H_kv·D]) with S off the lane axis — each append is a
    one-row sublane write, not a rewrite of the slot's layer slab — and
    holds no int8 copy the size of the pool or of a layer's slab: for
    phi3 (MHA, head_dim 96) and granite (GQA, head_dim 128)."""
    import re
    cfg = registry.get(name)
    params = jax.eval_shape(
        lambda: quantize_tree(M.init_params(jax.random.key(0), cfg,
                                            jnp.bfloat16)))
    state = jax.eval_shape(lambda: M.init_decode_state(cfg, slots, MAX_LEN))
    place = lambda t: jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype), t)
    rt = Runtime()
    hlo = jax.jit(
        lambda p, s, t: M.decode_step(p, cfg, s, t, rt), donate_argnums=(1,)
    ).lower(place(params), place(state),
            _sds(one_chip, (slots,), jnp.int32)).compile().as_text()
    seq = r"s8\[(?:\d+,)*%d(?:,\d+)*\]" % MAX_LEN     # pool or slab
    appends = re.findall(r"= s8\[%d,%d,%d,[\d,]+\]\{([\d,]+)[:}]"
                         r"\S* dynamic-update-slice\(" % (
                             cfg.n_layers, slots, MAX_LEN), hlo)
    assert appends                      # the K and V appends into the pool
    assert all(m2m.split(",")[0] != "2" for m2m in appends), appends
    assert not [l for l in hlo.splitlines()
                if re.search(r"= %s\S* copy\(" % seq, l)]


def test_phi3_mesh_decode_step_appends_per_shard(topo):
    """On the 2x2 serve mesh, with the engine's shardings (slots over
    `data`, KV heads over `model`), each device appends its own slots' rows:
    the compiled step moves no pool-sized array between chips and copies
    none, and the pool spec the append assumes is the one the engine pins."""
    import re
    import numpy as np
    from jax.sharding import AxisType, Mesh
    from repro.configs.shapes import ShapeConfig
    from repro.dist import sharding as SH
    cfg = registry.get("phi3-mini-3.8b")
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    rt = Runtime(mesh=mesh, data_axes=("data",), serve_resident_moe=True)
    params = jax.eval_shape(
        lambda: quantize_tree(M.init_params(jax.random.key(0), cfg,
                                            jnp.bfloat16)))
    state = jax.eval_shape(lambda: M.init_decode_state(cfg, SLOTS, MAX_LEN))
    psh = SH.param_shardings(cfg, params, mesh, serve=True)
    ssh = SH.decode_state_shardings(
        cfg, ShapeConfig("serve", MAX_LEN, SLOTS, "decode"), state, mesh)
    io = SH.serve_step_shardings(SLOTS, mesh)
    pool_spec = SH.kv_pool_spec(SLOTS, cfg.n_kv_heads, mesh)
    pools = ssh["groups"][0][0]
    assert {n: s.spec for n, s in pools.items()} == pool_spec
    place = lambda t, sh: jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        t, sh)
    hlo = jax.jit(
        lambda p, s, t: M.decode_step(p, cfg, s, t, rt),
        in_shardings=(psh, ssh, io["tokens"]),
        out_shardings=(io["logits"], ssh), donate_argnums=(1,),
    ).lower(place(params, psh), place(state, ssh),
            jax.ShapeDtypeStruct((SLOTS,), jnp.int32,
                                 sharding=io["tokens"])).compile().as_text()
    moved = re.compile(r"= (s8|f32)\[\d+,\d+,%d(,\d+)+\]\S* (all-gather|"
                       r"all-reduce|all-to-all|collective-permute|"
                       r"reduce-scatter)" % MAX_LEN)
    copied = re.compile(r"= s8\[\d+,\d+,%d(,\d+)+\]\S* copy\(" % MAX_LEN)
    lines = hlo.splitlines()
    assert not [l for l in lines if moved.search(l)]
    assert not [l for l in lines if copied.search(l)]


def test_deepseek_share_serves_in_hbm(one_chip):
    """One EP32 chip's share of DeepSeek-V3 (3 dense and 4 MoE layers of 8
    held experts, an eighth of the vocabulary) at published widths: the
    bf16 prefill weights, the W8A8 decode weights and the 32-slot latent
    pool sit in one v5e beside the larger of the counted decode step and
    the longest prefill's temporaries."""
    import dataclasses
    cfg = dataclasses.replace(registry.get("deepseek-v3-671b"), n_layers=7,
                              n_experts_held=8, expert_shard=5,
                              vocab_size=16160, mtp=False)
    params = jax.eval_shape(
        lambda: M.init_params(jax.random.key(0), cfg, jnp.bfloat16))
    qparams = jax.eval_shape(quantize_tree, params)
    state = jax.eval_shape(lambda: M.init_decode_state(
        cfg, 32, 2048 + KV.pool_headroom()))
    place = lambda t: jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype), t)
    rt = Runtime()
    decode = jax.jit(
        lambda p, s, t: M.decode_step(p, cfg, s, t, rt, counts=True),
        donate_argnums=(1,)).lower(
            place(qparams), place(state),
            _sds(one_chip, (32,), jnp.int32)).compile().memory_analysis()
    batch = {"inputs": _sds(one_chip, (1, 1536), jnp.int32),
             "lengths": _sds(one_chip, (1,), jnp.int32)}
    prefill = jax.jit(
        lambda p, b: M.prefill(p, cfg, b, 2048, rt, counts=True)).lower(
            place(params), batch).compile().memory_analysis()
    nbytes = lambda leaves: sum(a.size * a.dtype.itemsize for a in leaves)
    # qparams keep the float leaves they do not quantize (embedding, head,
    # norms, router) as the same arrays as params: only the int8 weights
    # and their scales are new
    new = [a for path, a in jax.tree_util.tree_flatten_with_path(qparams)[0]
           if str(path[-1].key).endswith(("_q", "_s"))]
    resident = (nbytes(jax.tree.leaves(params)) + nbytes(new)
                + nbytes(jax.tree.leaves(state)))
    assert decode.alias_size_in_bytes > 0        # the pool updates in place
    peak = resident + max(decode.temp_size_in_bytes, prefill.temp_size_in_bytes)
    assert peak < V5E_HBM_BYTES, (resident, decode, prefill)
