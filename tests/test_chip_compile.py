"""The main path's kernels compile for the chip — checked without one.

The TPU compiler is installed here and compiles for a DESCRIBED
``v5e:2x2`` topology (nothing is attached, nothing runs): it raises what
the real chip's compiler would raise — block shapes Mosaic cannot tile,
kernels over the VMEM budget, programs over HBM — which Pallas interpret
mode, the rest of this suite, cannot see.  A compile that passes is not
a chip run; ``chip_smoke.py`` is.

This is the ONLY test file that describes a chip.  Only one process may
load the TPU library, and it keeps it until it exits, so the topology is
described inside a module-scoped fixture (never at import: every xdist
worker imports every test file) and every compile happens in the test's
own process.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distkeras_tpu.ops import (gated_delta, pallas_attention, pallas_gdn,
                               pallas_moe, pallas_ssm, ssm)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever the describe raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on the first described chip.  The persistent compile
    cache is off while this module compiles: an entry written for a
    described device cannot be read back without the chip, and the next
    compile would warn about it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture()
def mosaic(monkeypatch):
    """The kernels as the chip gets them: the backend here is the CPU, so
    ``_interpret()`` would pick interpret mode and no kernel would reach
    the TPU compiler."""
    monkeypatch.setattr(pallas_attention, "_interpret", lambda: False)
    monkeypatch.setattr(pallas_moe, "_interpret", lambda: False)
    monkeypatch.setattr(pallas_ssm, "_interpret", lambda: False)
    monkeypatch.setattr(pallas_gdn, "_interpret", lambda: False)


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


def _qkv(one_chip, b, t, h, dh, dtype):
    return (jax.ShapeDtypeStruct((b, t, h, dh), jnp.dtype(dtype),
                                 sharding=one_chip),) * 3


def _sq_loss(attn):
    return lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)


#: (B, T, H, Dh), dtype — GPT-2-small's attention (the smoke's model) in
#: both dtypes, the 1024² big-block regime, head_dim 128, a small f32;
#: and where the causal rule switches path (``_causal_tile``): T = 640
#: walks 5 tiles of 128 inside the kernel (a count that is no power of
#: two), T = 4,096 is the first power of two past the VMEM budget and
#: takes the grid walk (its scalar-prefetched table of block pairs), as
#: the 8k cell's full layers do: 48 heads of 128, 136 steps a head
SHAPES = [
    ((2, 1024, 12, 64), "bfloat16"),
    ((2, 1024, 12, 64), "float32"),
    ((1, 8192, 4, 64), "bfloat16"),
    ((1, 2048, 8, 128), "bfloat16"),
    ((4, 256, 4, 32), "float32"),
    ((2, 640, 4, 64), "bfloat16"),
    ((1, 4096, 4, 64), "bfloat16"),
    ((1, 8192, 48, 128), "bfloat16"),
]


@pytest.mark.parametrize("mode", ["fwd", "fwd_bwd"])
@pytest.mark.parametrize("shape,dtype", SHAPES,
                         ids=[f"{'x'.join(map(str, s))}-{d}"
                              for s, d in SHAPES])
def test_flash_attention_compiles(one_chip, mosaic, shape, dtype, mode):
    def attn(q, k, v):
        return pallas_attention.flash_attention(q, k, v, True)

    fn = attn if mode == "fwd" else jax.grad(_sq_loss(attn),
                                             argnums=(0, 1, 2))
    _compile(fn, *_qkv(one_chip, *shape, dtype))


@pytest.mark.parametrize("shape,dtype,mode", [
    ((1, 8192, 64, 128), "bfloat16", "fwd_bwd"),  # a training step's
    ((2, 8192, 64, 128), "float32", "fwd"),       # the reference check's
], ids=["train-bf16", "check-f32"])
def test_window_attention_compiles(one_chip, mosaic, shape, dtype, mode):
    """The sliding-window walk at the 8k cell's shape: 64 heads of 128,
    window 512, 512-blocks, 2 of 16 key blocks a query block."""
    def attn(q, k, v):
        return pallas_attention.flash_attention(q, k, v, True, None, None,
                                                512)

    fn = attn if mode == "fwd" else jax.grad(_sq_loss(attn),
                                             argnums=(0, 1, 2))
    text = _compile(fn, *_qkv(one_chip, *shape, dtype))
    # the instruction's name is what a trace row reads
    assert "%window_attn_fwd" in text and "%flash_fwd" not in text


@pytest.mark.parametrize("heads,kv,window", [
    (48, 8, None), (64, 8, 512), (32, 2, None)],
    ids=["laguna-full", "laguna-window", "nemotron"])
def test_attention_on_its_own_kv_heads_compiles(one_chip, mosaic, heads, kv,
                                                window):
    """The 8k cells' attention with K and V at their own head count: the
    forward's and dQ's K/V index maps divide the head, dK/dV leads with
    the K/V heads and walks a group's query heads a row (a table of
    136 × 6 and 136 × 16 steps scalar-prefetched, or one grid axis more
    on the band); nothing at the query heads' size is a broadcast."""
    def attn(q, k, v):
        return pallas_attention.flash_attention(q, k, v, True, None, None,
                                                window)

    q, _, _ = _qkv(one_chip, 1, 8192, heads, 128, "bfloat16")
    k, v, _ = _qkv(one_chip, 1, 8192, kv, 128, "bfloat16")
    text = _compile(jax.grad(_sq_loss(attn), argnums=(0, 1, 2)), q, k, v)
    assert text.count("tpu_custom_call") >= 3
    assert f"bf16[1,8192,{kv},{heads // kv},128]" not in text


@pytest.mark.parametrize("dtype,tokens,mode", [
    ("bfloat16", 8192, "fwd_bwd"), ("float32", 16384, "fwd")],
    ids=["train-bf16", "check-f32"])
def test_grouped_matmuls_compile(one_chip, mosaic, dtype, tokens, mode):
    """The routed experts' row buffer at the 8k cell's shape: 8 choices a
    token, 32 experts of (2048 x 2 x 512) and (512 x 2048) held here."""
    rows = tokens * 8 + 32 * pallas_moe.TILE_ROWS

    def shape(*dims, dt=dtype):
        return jax.ShapeDtypeStruct(dims, jnp.dtype(dt), sharding=one_chip)

    def experts(x, gate_up, down, tile_expert, num_tiles):
        h = pallas_moe.grouped_matmul(x, gate_up, tile_expert, num_tiles)
        a = jax.nn.silu(h[:, :512]) * h[:, 512:]
        return jnp.sum(pallas_moe.grouped_matmul(
            a, down, tile_expert, num_tiles).astype(jnp.float32) ** 2)

    fn = experts if mode == "fwd" else jax.grad(experts, argnums=(0, 1, 2))
    text = _compile(fn, shape(rows, 2048), shape(32, 2048, 1024),
                    shape(32, 512, 2048),
                    shape(rows // pallas_moe.TILE_ROWS, dt="int32"),
                    shape(1, dt="int32"))
    assert "moe_gmm" in text and ("moe_tgmm" in text) == (mode != "fwd")


@pytest.mark.parametrize("dtype,tokens,mode", [
    ("bfloat16", 8192, "fwd_bwd"), ("float32", 16384, "fwd")],
    ids=["train-bf16", "check-f32"])
def test_grouped_matmuls_compile_at_widths_128_does_not_divide(
        one_chip, mosaic, dtype, tokens, mode):
    """The Nemotron cell's row buffer: 6 choices a token, 8 relu² experts
    of (2688 x 1856) and (1856 x 2688) held here.  1,856 is one block, so
    the depth of 2,688 is tiled (an accumulator over contraction steps)
    and ``moe_tgmm``'s accumulator takes a block of columns at a time: a
    whole (2688, 1856) block twice buffered is 20 MB of v5e's 16."""
    rows = tokens * 6 + 8 * pallas_moe.TILE_ROWS

    def shape(*dims, dt=dtype):
        return jax.ShapeDtypeStruct(dims, jnp.dtype(dt), sharding=one_chip)

    def experts(x, up, down, tile_expert, num_tiles):
        h = pallas_moe.grouped_matmul(x, up, tile_expert, num_tiles)
        return jnp.sum(pallas_moe.grouped_matmul(
            jnp.square(jax.nn.relu(h)), down, tile_expert,
            num_tiles).astype(jnp.float32) ** 2)

    fn = experts if mode == "fwd" else jax.grad(experts, argnums=(0, 1, 2))
    text = _compile(fn, shape(rows, 2688), shape(8, 2688, 1856),
                    shape(8, 1856, 2688),
                    shape(rows // pallas_moe.TILE_ROWS, dt="int32"),
                    shape(1, dt="int32"))
    assert "moe_gmm" in text and ("moe_tgmm" in text) == (mode != "fwd")


@pytest.mark.parametrize("tokens,width,rows,dtype", [
    (8192, 2688, 56 * 128, "bfloat16"), (8192, 2048, 160 * 128, "bfloat16"),
    (16384, 2688, 104 * 128, "float32")],
    ids=["nemotron-train", "laguna-train", "nemotron-check-f32"])
def test_rows_to_tokens_compiles(one_chip, mosaic, tokens, width, rows,
                                 dtype):
    """A round's rows added into the tokens' float32 sum, a column block
    of the WHOLE sum resident in VMEM (384 or 512 columns of 8,192 or
    16,384 tokens: 12.6 to 25 MB, twice buffered, over the scoped default
    that the call raises) and a dynamic sublane a row."""
    block = pallas_moe.rows_to_tokens_block(tokens, width)
    assert block == {2688: 384, 2048: 512}[width]

    def shape(*dims, dt=dtype):
        return jax.ShapeDtypeStruct(dims, jnp.dtype(dt), sharding=one_chip)

    text = _compile(
        lambda r, s, t, n: pallas_moe.rows_to_tokens(
            r, s, t, n, n=tokens, block=block),
        shape(rows, width), shape(rows, dt="float32"),
        shape(rows, dt="int32"), shape(1, dt="int32"))
    assert "moe_rows_to_tokens" in text
    # a sum that no 128-column block of fits: the caller's scatter-add
    assert pallas_moe.rows_to_tokens_block(65536, 2048) is None


@pytest.mark.parametrize("dtype,batch,mode", [
    ("bfloat16", 1, "fwd_bwd"), ("float32", 2, "fwd")],
    ids=["train-bf16", "check-f32"])
def test_scan_kernels_compile(one_chip, mosaic, dtype, batch, mode):
    """The Mamba-2 scan at the Nemotron cell's shape: 64 heads of 64 in 8
    groups, state 128, 64 chunks of 128 a row of 8,192."""
    def shape(*dims, dt=dtype):
        return jax.ShapeDtypeStruct(dims, jnp.dtype(dt), sharding=one_chip)

    def scan(*args):
        return ssm.ssd(*args, chunk=128, impl="pallas")

    fn = scan if mode == "fwd" else jax.grad(
        lambda *args: jnp.sum(scan(*args).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2, 3, 4))
    text = _compile(fn, shape(batch, 8192, 64, 64),
                    shape(batch, 8192, 64, dt="float32"),
                    shape(64, dt="float32"), shape(batch, 8192, 8, 128),
                    shape(batch, 8192, 8, 128))
    # the instructions' names are what a trace's rows read
    assert "%ssd_chunk_fwd" in text
    assert ("%ssd_chunk_bwd" in text) == (mode != "fwd")


@pytest.mark.parametrize("dtype,batch,mode", [
    ("bfloat16", 1, "fwd_bwd"), ("float32", 2, "fwd")],
    ids=["train-bf16", "check-f32"])
def test_delta_rule_kernels_compile(one_chip, mosaic, dtype, batch, mode):
    """The gated delta rule at the Olmo Hybrid cell's shape: 30 heads,
    keys of 96 and values of 192 (neither a multiple of 128 lanes), 128
    chunks of 64 a row of 8,192."""
    def shape(*dims, dt=dtype):
        return jax.ShapeDtypeStruct(dims, jnp.dtype(dt), sharding=one_chip)

    def rule(*args):
        return gated_delta.gated_delta_rule(*args, chunk=64, impl="pallas")

    fn = rule if mode == "fwd" else jax.grad(
        lambda *args: jnp.sum(rule(*args).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2, 3, 4))
    text = _compile(fn, shape(batch, 8192, 30, 96), shape(batch, 8192, 30, 96),
                    shape(batch, 8192, 30, 192),
                    shape(batch, 8192, 30, dt="float32"),
                    shape(batch, 8192, 30, dt="float32"))
    # the instructions' names are what a trace's rows read
    assert "%gdn_chunk_fwd" in text
    assert ("%gdn_chunk_bwd" in text) == (mode != "fwd")


def test_flash_lse_rectangular_hop_compiles(one_chip, mosaic):
    """Tq != Tk, non-causal: the zigzag ring's half-block hop."""
    q = jax.ShapeDtypeStruct((2, 1024, 4, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 512, 4, 64), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        o, lse = pallas_attention.flash_attention_lse(q, k, v, False)
        return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(lse)

    _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)


@pytest.mark.parametrize("t", [200, 544])
def test_causal_awkward_length_compiles(one_chip, mosaic, t):
    """T=200 / T=544 have no 128-aligned divisor; Mosaic refused the
    100- and 68-wide blocks the old rule handed it.  The causal route pads to a multiple of 128."""
    from distkeras_tpu.ops.attention import _flash_with_blocking

    def attn(q, k, v):
        return _flash_with_blocking(q, k, v, True, t)

    _compile(jax.grad(_sq_loss(attn), argnums=(0, 1, 2)),
             *_qkv(one_chip, 2, t, 4, 64, "bfloat16"))


def test_noncausal_awkward_length_refused(one_chip, mosaic):
    """Padding would let real queries attend the padded keys: the
    non-causal route refuses before anything reaches the compiler."""
    from distkeras_tpu.ops.attention import _flash_with_blocking

    def attn(q, k, v):
        return _flash_with_blocking(q, k, v, False, 200)

    with pytest.raises(ValueError, match="block-sized divisor"):
        jax.jit(attn).lower(*_qkv(one_chip, 2, 200, 4, 64, "bfloat16"))


def test_gpt_train_step_compiles_with_kernel(one_chip, mosaic):
    """One whole ``make_local_step`` at GPT-2-small widths (depth cut to
    2 blocks), bf16 compute: the program the trainers run carries the
    Mosaic kernel, fits the chip, and its flash kernels run the causal
    triangle only."""
    import optax

    from distkeras_tpu.models import zoo
    from distkeras_tpu.ops.losses import sparse_categorical_crossentropy
    from distkeras_tpu.parallel.sync import make_local_step

    batch, seq, vocab = 8, 1024, 50257
    model = zoo.gpt_lm(vocab_size=vocab, dim=768, num_heads=12,
                       num_blocks=2, seq_len=seq, attention_impl="flash")
    optimizer = optax.adam(1e-3)
    step = make_local_step(model, sparse_categorical_crossentropy,
                           optimizer, compute_dtype=jnp.bfloat16)

    def carry_shapes():
        variables = model.init(0)
        return (variables, optimizer.init(variables["params"]),
                jax.random.PRNGKey(0))

    def on_chip(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    carry = jax.tree_util.tree_map(on_chip, jax.eval_shape(carry_shapes))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                  sharding=one_chip)
    from distkeras_tpu.obs.registry import default_registry
    tiles = [default_registry().counter(f"flash.causal_tiles_{which}")
             for which in ("executed", "total")]
    before = [c.value for c in tiles]
    compiled = jax.jit(step, donate_argnums=0).lower(
        carry, (tokens, tokens)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # one trace of the step: 2 blocks x 3 kernels, each 10 of 16 tile
    # pairs (T = 1,024 in 256 tiles: the in-kernel causal walk engaged)
    assert [c.value - b for c, b in zip(tiles, before)] == [60, 96]
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 16e9, f"{total / 1e9:.1f} GB does not fit 16 GB of HBM"
