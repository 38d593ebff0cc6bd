"""TPU lowering AND compile regression tests — no hardware required.

The Pallas kernels run under interpret=True everywhere on CPU, so a bug
that only the TPU toolchain rejects never surfaces in the normal suite.
Two stages can reject a kernel, and this file pins both:

- *Lowering* (`_export_tpu`): `jax.export.export(..., platforms=['tpu'])`
  lowers the Pallas call to a `tpu_custom_call` carrying the Mosaic
  module. It catches BlockSpec/tiling rules (the rank-2 operands must
  ride as rank-3 with singleton middle dims) but STOPS there: Mosaic's
  own passes — vector-layout inference, VMEM allocation — run inside the
  XLA:TPU compile, which export never reaches. A kernel that exported
  cleanly (`(m <= NEG / 2)[:, None]`, a reshape of an i1 vector) failed
  `infer-vector-layout` at its first real compile.
- *Compiling* (`_compile_v5e`): libtpu ships the XLA:TPU and Mosaic
  compilers, and `jax.experimental.topologies.get_topology_desc` hands
  out `TPU v5 lite` devices without a chip. `jit(f).lower(<shapes placed
  on those devices>).compile()` runs the real compile here under
  JAX_PLATFORMS=cpu. Run the same pre-check on any new program before
  spending chip time on it.

Reference anchor: the cuDNN-helper seam these kernels replace
(deeplearning4j-cuda/.../CudnnConvolutionHelper.java) has no CPU-side
validation either — this is the TPU-native improvement on that story.
"""
import jax
import jax.numpy as jnp
import pytest


def _export_tpu(fn, *args, expect_pallas: bool = True):
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    if expect_pallas:
        mlir = exported.mlir_module()
        assert "tpu_custom_call" in mlir, (
            "exported module contains no Mosaic kernel — the Pallas path "
            "was not taken (interpret-mode emulation lowered instead)")
    return exported


class TestFlashKernelLowering:
    def test_forward_causal_bf16(self):
        from deeplearning4j_tpu.ops.flash_attention import flash_attention
        q = jnp.zeros((2, 512, 4, 64), jnp.bfloat16)
        _export_tpu(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False), q, q, q)

    def test_forward_masked_with_padding(self):
        # t=300 is not a multiple of the 128 block: exercises the
        # internal pad path (padded keys mask-excluded) under Mosaic
        from deeplearning4j_tpu.ops.flash_attention import flash_attention
        q = jnp.zeros((2, 300, 4, 64), jnp.bfloat16)
        m = jnp.ones((2, 300), jnp.bfloat16)
        _export_tpu(lambda q, k, v, m: flash_attention(
            q, k, v, mask=m, interpret=False), q, q, q, m)

    def test_backward_kernels_with_lse_cotangent(self):
        # grad through out AND lse covers the backward kernel (dq, dk and
        # dv in one walk) and the lse-cotangent fold into delta
        from deeplearning4j_tpu.ops.flash_attention import flash_attention

        def loss(q, k, v):
            o, lse = flash_attention(q, k, v, causal=True,
                                     interpret=False, return_lse=True)
            return jnp.sum(o.astype(jnp.float32)) + jnp.sum(lse)

        q = jnp.zeros((2, 512, 4, 64), jnp.bfloat16)
        _export_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)

    def test_cross_attention_shapes(self):
        from deeplearning4j_tpu.ops.flash_attention import flash_attention
        q = jnp.zeros((2, 256, 4, 64), jnp.bfloat16)
        k = jnp.zeros((2, 1024, 4, 64), jnp.bfloat16)

        def loss(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, interpret=False).astype(jnp.float32))

        _export_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, k)


def _ring_flash(mesh):
    import functools
    from jax.sharding import PartitionSpec as P
    from deeplearning4j_tpu.parallel.ring import (
        ring_flash_self_attention, SEQ_AXIS)
    # interpret=False forced: the default resolves against the CPU
    # backend at trace time and would lower the emulation instead
    return jax.shard_map(
        functools.partial(ring_flash_self_attention, causal=True,
                          interpret=False),
        mesh=mesh,
        in_specs=(P(None, SEQ_AXIS), P(None, SEQ_AXIS), P(None, SEQ_AXIS)),
        out_specs=P(None, SEQ_AXIS), check_vma=False)


class TestRingFlashLowering:
    def test_ring_flash_over_seq_mesh(self):
        from jax.sharding import Mesh
        from deeplearning4j_tpu.parallel.ring import SEQ_AXIS
        q = jnp.zeros((2, 512, 4, 64), jnp.bfloat16)
        _export_tpu(_ring_flash(Mesh(jax.devices()[:4], (SEQ_AXIS,))),
                    q, q, q)


@pytest.fixture(scope="module")
def v5e():
    """A 2x2 TPU v5e topology from libtpu's compile-only client."""
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    # keep these programs out of the persistent cache: the compile-only
    # client can store an executable but not load one back
    key = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, key)
    jax.config.update(key, 1e9)
    yield topo
    jax.config.update(key, before)


def _compile_v5e(fn, sharding, *avals):
    """The real XLA:TPU + Mosaic compile of fn at `avals` ((shape,
    dtype) pairs placed by `sharding`); returns the compiled HLO text."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in avals]
    return jax.jit(fn).lower(*args).compile().as_text()


#: the kernels a flash program compiles to: the forward alone (False), or
#: with the backward in one of its two forms (conftest's `flash_backward`)
_FLASH_KERNELS = {False: ["flash_fwd"], "fused": ["flash_bwd_dq", "flash_fwd"],
                  "pair": ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]}


def _flash_kernels(monkeypatch, backward):
    """`_FLASH_KERNELS` of a test parametrised over (False, "fused",
    "pair") itself; the pair is forced as the fixture forces it."""
    import sys

    import deeplearning4j_tpu.ops  # noqa: F401  (the module is loaded)
    if backward == "pair":
        monkeypatch.setattr(
            sys.modules["deeplearning4j_tpu.ops.flash_attention"],
            "_RESIDENT_SUM_BYTES", 0)
    return _FLASH_KERNELS[backward]


def _kernels_called(hlo):
    """The Pallas kernels a compiled module calls, by instruction name."""
    import re
    return sorted(re.findall(
        r"^\s*(?:ROOT\s+)?%?(\w+?)(?:\.\d+)? = [^\n]*tpu_custom_call",
        hlo, re.M))


def _holds_flash_kernels(hlo, kernels):
    """The compiled text names exactly ``kernels`` of the three flash
    kernels (a bare gradient's instructions are named after their
    transforms, `transpose_jvp_flash_bwd_dq`, so by substring)."""
    return "tpu_custom_call" in hlo and all(
        (name in hlo) == (name in kernels)
        for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))


def _loop_trips(hlo):
    """The bound each `while` of a compiled module counts to: the constant
    its condition computation (or the fusion that one calls) compares
    with. The TPU's text carries no trip count."""
    import re

    def body(name):
        return re.search(r"^%?" + re.escape(name) + r" \([^\n]*\{\n(.*?)^\}",
                         hlo, re.M | re.S).group(1)

    trips = []
    for cond in re.findall(r" while\(.*?condition=%?([\w.\-]+)", hlo):
        text = body(cond)
        text += "".join(body(c)
                        for c in re.findall(r"calls=%?([\w.\-]+)", text))
        trips += [int(n) for n in re.findall(r"constant\((\d+)\)", text)]
    return trips


class TestFlashKernelCompiles:
    """forward and backward (the fused kernel, and the dq and dk/dv pair
    of a call past its byte budget) through Mosaic's own passes, at the
    smoke's shapes and the layer-default block 512."""

    @staticmethod
    def _one(v5e):
        from jax.sharding import SingleDeviceSharding
        return SingleDeviceSharding(v5e.devices[0])

    def test_forward_causal_bf16(self, v5e):
        from deeplearning4j_tpu.ops.flash_attention import flash_attention
        qkv = ((4, 2048, 8, 64), jnp.bfloat16)
        hlo = _compile_v5e(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False),
            self._one(v5e), qkv, qkv, qkv)
        assert "tpu_custom_call" in hlo and "flash_fwd" in hlo

    @pytest.mark.parametrize("d,block", [(64, 128), (128, 512)])
    def test_grad_compiles_dq_and_dkv(self, v5e, d, block, flash_backward):
        from deeplearning4j_tpu.ops.flash_attention import flash_attention

        def loss(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, block_q=block, block_k=block,
                interpret=False).astype(jnp.float32) ** 2)

        qkv = ((4, 2048, 8, d), jnp.bfloat16)
        hlo = _compile_v5e(jax.grad(loss, argnums=(0, 1, 2)),
                           self._one(v5e), qkv, qkv, qkv)
        assert _holds_flash_kernels(hlo, _FLASH_KERNELS[flash_backward])

    @pytest.mark.parametrize("heads,d_qk,d_v", [(32, 192, 128),
                                                (20, 256, 256)])
    @pytest.mark.parametrize("backward", ["fused", "pair", False])
    @pytest.mark.parametrize("masked", [False, True])
    def test_two_head_sizes_at_the_lm_cells_widths(self, v5e, backward,
                                                   heads, d_qk, d_v, masked,
                                                   monkeypatch):
        # latent attention's expanded form at the two LM cells' widths:
        # 32 heads of 192-wide q.k and 128-wide v (position-free), 20 of
        # 256 / 256 (rotated); 8,192 positions, block 512. Without a key
        # mask (the cells) the kernels take no mask operand and lower an
        # interior and a diagonal body; with one, the one body that
        # applies it on every tile
        from deeplearning4j_tpu.ops.flash_attention import flash_attention
        kernels = _flash_kernels(monkeypatch, backward)

        def loss(q, k, v, *mask):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, block_q=512, block_k=512,
                mask=mask[0] if mask else None,
                interpret=False).astype(jnp.float32) ** 2)

        qk = ((1, 8192, heads, d_qk), jnp.bfloat16)
        v = ((1, 8192, heads, d_v), jnp.bfloat16)
        mask = [((1, 8192), jnp.float32)] if masked else []
        hlo = _compile_v5e(
            jax.grad(loss, argnums=(0, 1, 2)) if backward else loss,
            self._one(v5e), qk, qk, v, *mask)
        assert _holds_flash_kernels(hlo, kernels)

    @pytest.mark.parametrize("backward", ["fused", "pair", False])
    @pytest.mark.parametrize("masked", [False, True])
    def test_grouped_64_wide_heads_at_the_third_lm_cells_widths(
            self, v5e, backward, masked, monkeypatch):
        # grouped-query attention at the LFM2 cell's widths: 32 query
        # heads on 8 key/value heads, 64 wide (half the v5e's lanes), 4
        # sequences of 8,192 positions, block 512; k, v and their
        # gradients keep 8 heads; without a key mask (the cell) and with
        from deeplearning4j_tpu.ops.flash_attention import flash_attention
        kernels = _flash_kernels(monkeypatch, backward)

        def loss(q, k, v, *mask):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, block_q=512, block_k=512,
                mask=mask[0] if mask else None,
                interpret=False).astype(jnp.float32) ** 2)

        q = ((4, 8192, 32, 64), jnp.bfloat16)
        kv = ((4, 8192, 8, 64), jnp.bfloat16)
        mask = [((4, 8192), jnp.float32)] if masked else []
        hlo = _compile_v5e(
            jax.grad(loss, argnums=(0, 1, 2)) if backward else loss,
            self._one(v5e), q, kv, kv, *mask)
        assert _holds_flash_kernels(hlo, kernels)

    @pytest.mark.parametrize("backward", ["fused", "pair", False])
    def test_the_block_diffusion_rule_at_the_sdar_cells_widths(
            self, v5e, backward, monkeypatch):
        # the rule of block-diffusion training at the SDAR cell's widths:
        # 32 query heads on 4 key/value heads, 128 wide, 2 sequences whose
        # stream [noisy ; clean] is 16,384 rows, block 512, diffusion
        # blocks of 4 (the in-tile predicate divides positions by it on
        # the vector unit); the index maps walk two runs of tiles
        from deeplearning4j_tpu.ops.flash_attention import flash_attention
        kernels = _flash_kernels(monkeypatch, backward)

        def loss(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, block_diffusion=4, block_q=512, block_k=512,
                interpret=False).astype(jnp.float32) ** 2)

        q = ((2, 16384, 32, 128), jnp.bfloat16)
        kv = ((2, 16384, 4, 128), jnp.bfloat16)
        hlo = _compile_v5e(
            jax.grad(loss, argnums=(0, 1, 2)) if backward else loss,
            self._one(v5e), q, kv, kv)
        assert _holds_flash_kernels(hlo, kernels)

    def test_checkpointed_grouped_query_attention_runs_the_forward_once(
            self, v5e, monkeypatch, flash_backward):
        # `MultiHeadAttention` as the LFM2 family holds it (32 on 8, q/k
        # normed, rotated at 1e6, the fused kernel) inside the containers'
        # rematerialised layer call: ONE flash_fwd in the gradient, and no
        # k or v of 32 heads anywhere (they are not repeated in HBM)
        import sys

        from deeplearning4j_tpu.nn.conf.base import InputType
        from deeplearning4j_tpu.nn.layers import MultiHeadAttention
        from deeplearning4j_tpu.nn.multilayer import _layer_call
        for name in ("ops.flash_attention", "nn.layers.attention"):
            monkeypatch.setattr(sys.modules["deeplearning4j_tpu." + name],
                                "is_tpu_backend", lambda: True)
        layer = MultiHeadAttention(
            n_out=2048, n_heads=32, n_kv_heads=8, causal=True, use_rope=True,
            rope_base=1e6, qk_norm=True, has_bias=False,
            attention_impl="flash", block_size=512)
        shapes = jax.eval_shape(
            lambda key: layer.init(key, InputType.recurrent(2048, 8192),
                                   jnp.bfloat16)[0], jax.random.PRNGKey(0))

        def loss(params, x):
            y, _ = _layer_call(layer, seq=False, train=True, remat=True,
                               params=params, x=x, state={})
            return jnp.sum(y.astype(jnp.float32) ** 2)

        one = self._one(v5e)
        place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one)
        hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            jax.tree_util.tree_map(place, shapes),
            place(jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16))
        ).compile().as_text()
        assert _kernels_called(hlo) == _FLASH_KERNELS[flash_backward]
        calls = [line for line in hlo.splitlines()
                 if "tpu_custom_call" in line]
        assert all("bf16[8,8192,64]" in line for line in calls)   # k, v

    @pytest.mark.parametrize("hidden,widths", [
        (2304, dict(n_heads=32, nope_dim=128, rope_dim=64, v_dim=128)),
        (2048, dict(n_heads=20, nope_dim=192, rope_dim=64, v_dim=256,
                    q_rank=768, rotate=True))])
    def test_checkpointed_latent_attention_runs_the_forward_once(
            self, v5e, hidden, widths, monkeypatch, flash_backward):
        # the latent attention of each LM cell (32 x 192/128 position-free;
        # 20 x 256/256 rotated with a low-rank query) inside the
        # containers' rematerialised layer call: the kernel's output and
        # log-sum-exp are kept, so the gradient holds ONE flash_fwd
        import sys

        from deeplearning4j_tpu.nn.conf.base import InputType
        from deeplearning4j_tpu.nn.layers.linear_attention import (
            MultiHeadLatentAttention,
        )
        from deeplearning4j_tpu.nn.multilayer import _layer_call
        for name in ("ops.flash_attention", "nn.layers.linear_attention"):
            monkeypatch.setattr(sys.modules["deeplearning4j_tpu." + name],
                                "is_tpu_backend", lambda: True)
        layer = MultiHeadLatentAttention(n_out=hidden, kv_rank=512,
                                         **widths)
        shapes = jax.eval_shape(
            lambda key: layer.init(key, InputType.recurrent(hidden, 8192),
                                   jnp.bfloat16)[0], jax.random.PRNGKey(0))

        def loss(params, x):
            y, _ = _layer_call(layer, seq=False, train=True, remat=True,
                               params=params, x=x, state={})
            return jnp.sum(y.astype(jnp.float32) ** 2)

        one = self._one(v5e)
        place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one)
        hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            jax.tree_util.tree_map(place, shapes),
            place(jax.ShapeDtypeStruct((1, 8192, hidden), jnp.bfloat16))
        ).compile().as_text()
        assert _kernels_called(hlo) == _FLASH_KERNELS[flash_backward]

    def test_expert_grouped_products_compile_as_ragged_dot(self, v5e):
        # the expert layer's grouped product at the cell's widths (one
        # dispatch block's worst-case rows): XLA's own tiled kernel over
        # the rows that are in a group
        from deeplearning4j_tpu.nn.layers.attention import _grouped_matmul

        def loss(x, w, sizes):
            return jnp.sum(_grouped_matmul(x, w, sizes).astype(
                jnp.float32) ** 2)

        args = [jax.ShapeDtypeStruct(shape, dtype,
                                     sharding=self._one(v5e))
                for shape, dtype in (((32768, 2304), jnp.bfloat16),
                                     ((8, 2304, 1024), jnp.bfloat16),
                                     ((8,), jnp.int32))]
        hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            *args).compile().as_text()
        assert "ragged-dot" in hlo and "tpu_custom_call" in hlo

    @pytest.mark.parametrize("t,d,kernels", [(8192, 128, True),
                                             (512, 8, False)])
    def test_kda_chunked_scan_compiles_with_its_backward(self, v5e, t, d,
                                                         kernels,
                                                         monkeypatch):
        # one group of the cell's (sequence, head) pairs: 8 pairs, 8,192
        # positions in chunks of 64, d_k = d_v = 128, bf16 products: the
        # two Pallas kernels hand the state over themselves, so there is
        # no loop over the 128 chunks and no stack of states written a
        # turn. At a width the tiling does not take (8), neither kernel
        # and the `lax.scan` over the chunks.
        import re
        from deeplearning4j_tpu.nn.layers.linear_attention import kda_chunked
        from deeplearning4j_tpu.ops import kda_chunk
        monkeypatch.setattr(kda_chunk, "is_tpu_backend", lambda: True)

        # (the narrow case in chunks of 16: what it holds is the choice
        # of the executor and the scan's trips, and a tile of 64 unrolled
        # under XLA takes 30 s to compile)
        chunk = 64 if kernels else 16

        def loss(q, k, v, log_a, beta):
            o, s = kda_chunked(q, k, v, log_a, beta, chunk=chunk,
                               mm_dtype=jnp.bfloat16)
            return jnp.sum(o ** 2) + jnp.sum(s ** 2)

        wide = ((1, t, 8, d), jnp.float32)
        hlo = _compile_v5e(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                           self._one(v5e), wide, wide, wide, wide,
                           ((1, t, 8), jnp.float32))
        assert ("tpu_custom_call" in hlo) == kernels
        for kernel in ("kda_chunk_fwd", "kda_chunk_bwd"):
            assert (kernel in hlo) == kernels
        assert (t // chunk in _loop_trips(hlo)) == (not kernels)
        assert not re.search(
            r"f32\[128,8,128,128\]\S* dynamic-update-slice", hlo)

    @pytest.mark.parametrize("n,chunk,kernels", [(128, 128, True),
                                                 (32, 32, False)])
    def test_ssd_chunked_scan_compiles_with_its_backward(self, v5e, n, chunk,
                                                         kernels,
                                                         monkeypatch):
        # the Nemotron cell's Mamba-2 slice: 2 sequences of 8,192
        # positions in chunks of 128, 16 heads of 64 on ONE group of state
        # 128, bf16 products: the two Pallas kernels hand the heads' states
        # over themselves, so there is no loop over the 64 chunks. At a
        # state width the tiling does not take (32), neither kernel and the
        # `lax.scan` over the chunks.
        from deeplearning4j_tpu.ops import ssd_chunk
        monkeypatch.setattr(ssd_chunk, "is_tpu_backend", lambda: True)
        t = 8192 if kernels else 512

        def loss(x, dt, a, b, c):
            y, s = ssd_chunk.ssd_chunked(x, dt, a, b, c, chunk=chunk,
                                         mm_dtype=jnp.bfloat16)
            return jnp.sum(y ** 2) + jnp.sum(s ** 2)

        f32 = jnp.float32
        hlo = _compile_v5e(
            jax.grad(loss, argnums=(0, 1, 2, 3, 4)), self._one(v5e),
            ((2, t, 16, 64), f32), ((2, t, 16), f32), ((16,), f32),
            ((2, t, 1, n), f32), ((2, t, 1, n), f32))
        assert ("tpu_custom_call" in hlo) == kernels
        for kernel in ("ssd_chunk_fwd", "ssd_chunk_bwd"):
            assert (kernel in hlo) == kernels
        assert (t // chunk in _loop_trips(hlo)) == (not kernels)

    def test_a_checkpointed_mamba2_block_at_the_nemotron_cells_widths(
            self, v5e, monkeypatch):
        # a block of the seventh configuration's step as the containers
        # run it: `MixerBlock(Mamba2Mixer)` at 4,096 wide, 16 heads of 64 on
        # one group of state 128, taps of 4, 2 x 8,192 positions in bf16,
        # inside the rematerialised layer call: the forward kernel in the
        # forward pass and once more in the forward made again (where it
        # writes the states every chunk received), ONE backward kernel
        import sys

        from deeplearning4j_tpu.nn.conf.base import InputType
        from deeplearning4j_tpu.nn.layers import Mamba2Mixer, MixerBlock
        from deeplearning4j_tpu.nn.multilayer import _layer_call
        import deeplearning4j_tpu.ops.ssd_chunk  # noqa: F401
        monkeypatch.setattr(
            sys.modules["deeplearning4j_tpu.ops.ssd_chunk"],
            "is_tpu_backend", lambda: True)
        layer = MixerBlock(n_out=4096, mixer=Mamba2Mixer(
            n_out=4096, n_heads=16, head_dim=64, n_groups=1, state_dim=128,
            conv_kernel=4, chunk=128))
        shapes = jax.eval_shape(
            lambda key: layer.init(key, InputType.recurrent(4096, 8192),
                                   jnp.bfloat16)[0], jax.random.PRNGKey(0))

        def loss(params, x):
            y, _ = _layer_call(layer, seq=False, train=True, remat=True,
                               params=params, x=x, state={})
            return jnp.sum(y.astype(jnp.float32) ** 2)

        one = self._one(v5e)
        place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one)
        hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            jax.tree_util.tree_map(place, shapes),
            place(jax.ShapeDtypeStruct((2, 8192, 4096), jnp.bfloat16))
        ).compile().as_text()
        assert _kernels_called(hlo) == ["ssd_chunk_bwd", "ssd_chunk_fwd",
                                        "ssd_chunk_fwd"]
        calls = [line for line in hlo.splitlines()
                 if "tpu_custom_call" in line]
        # x' at its 16 heads of 64 in bf16, a chunk a tile
        assert all("bf16[2,64,16,128,64]" in line for line in calls)

    def test_a_checkpointed_block_on_four_streams_at_the_xing_cells_widths(
            self, v5e, monkeypatch):
        # a block of the eighth configuration's step as the containers run
        # it: `HyperConnectedBlock` on 4 streams of 3,584 around the latent
        # attention's 4 held heads of 128 + 64 / 128 under YaRN and the
        # dense MLP of 9,216, 1 x 8,192 positions in bf16, inside the
        # rematerialised layer call: ONE forward flash kernel (its output
        # is kept); the mixing's four kernels a sub-layer, the forward ones
        # twice (the mappings are made again) but the last writing side,
        # whose result the backward does not read
        import sys

        from deeplearning4j_tpu.nn.conf.base import InputType
        from deeplearning4j_tpu.nn.layers import (
            GatedMLP, HyperConnectedBlock, MultiHeadLatentAttention,
        )
        from deeplearning4j_tpu.nn.multilayer import _layer_call
        import deeplearning4j_tpu.ops.mhc_mix  # noqa: F401
        for name in ("ops.flash_attention", "ops.mhc_mix",
                     "nn.layers.linear_attention"):
            monkeypatch.setattr(sys.modules["deeplearning4j_tpu." + name],
                                "is_tpu_backend", lambda: True)
        layer = HyperConnectedBlock(
            n_out=3584, n_streams=4, norm_epsilon=1e-6,
            attn=MultiHeadLatentAttention(
                n_out=3584, n_heads=4, nope_dim=128, rope_dim=64, v_dim=128,
                kv_rank=512, q_rank=768, rotate=True, norm_epsilon=1e-6,
                rope_scaling="yarn", rope_factor=64.0,
                rope_original_max_position=4096, rope_mscale_all_dim=1.0),
            ffn=GatedMLP(n_out=3584, hidden=9216))
        shapes = jax.eval_shape(
            lambda key: layer.init(key, InputType.recurrent(14336, 8192),
                                   jnp.bfloat16), jax.random.PRNGKey(0))

        def loss(params, state, x):
            y, new = _layer_call(layer, seq=False, train=True, remat=True,
                                 params=params, x=x, state=state)
            return jnp.sum(y.astype(jnp.float32) ** 2), new

        one = self._one(v5e)
        place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one)
        compiled = jax.jit(jax.grad(loss, argnums=(0, 2), has_aux=True)).lower(
            *jax.tree_util.tree_map(place, shapes),
            place(jax.ShapeDtypeStruct((1, 8192, 14336), jnp.bfloat16))
        ).compile()
        hlo = compiled.as_text()
        assert _kernels_called(hlo) == sorted(
            ["flash_bwd_dq", "flash_fwd"] + 4 * ["mhc_pre_fwd"]
            + 3 * ["mhc_post_fwd"] + 2 * ["mhc_pre_bwd", "mhc_post_bwd"])
        # the streams at the layer's input, after its first sub-layer and
        # their cotangents: a few copies of 235 MB, not tens
        assert compiled.memory_analysis().temp_size_in_bytes < 4.0e9

    @pytest.mark.parametrize("backward", [False, True],
                             ids=["forward", "gradient"])
    def test_sparse_attention_kernels_at_32k(self, v5e, backward):
        """The five kernels of the learned sparse attention at the fourth
        LM cell's shapes: one sequence of 32,768, 32 query heads of 128 on
        4, an indexer of 16 heads of 64, 2,048 keys a query, q blocks of
        512 with every head in VMEM. The gradient runs each forward
        kernel but the loss's (its value is not asked for) and the ONE
        backward kernel, `dsa_attn_bwd` (the key side's sums in float32
        buffers in HBM, moved by the kernel's own copies)."""
        from deeplearning4j_tpu.ops import dsa_attention as D
        t, bf = 32768, jnp.bfloat16
        assert D.kernel_blocks(t, 512, 32, 128) == (512, 512)

        def loss(*a):
            out, kl, kept = D.sparse_attention(
                *a, topk=2048, block_k=512, kernels=True, interpret=False)
            return jnp.sum(out.astype(jnp.float32)) + jnp.mean(kl), kept

        fn = jax.grad(loss, argnums=tuple(range(6)), has_aux=True) \
            if backward else loss
        hlo = _compile_v5e(
            fn, self._one(v5e), ((1, t, 32, 128), bf), ((1, t, 4, 128), bf),
            ((1, t, 4, 128), bf), ((1, t, 16, 64), bf), ((1, t, 64), bf),
            ((1, t, 16), jnp.float32))
        assert "tpu_custom_call" in hlo
        want = {"dsa_index", "dsa_select", "dsa_attn_fwd"} | (
            {"dsa_attn_bwd"} if backward else {"dsa_kl_fwd"})
        import re
        assert set(re.findall(r"dsa_(?:index|select|attn_fwd|kl_fwd|"
                              r"attn_bwd\w*)", hlo)) == want

    def test_masked_padded_f32_with_lse(self, v5e):
        # t=200: the pad path; masked non-causal with the lse output and
        # its cotangent, in f32
        from deeplearning4j_tpu.ops.flash_attention import flash_attention

        def loss(q, k, v, m):
            o, lse = flash_attention(q, k, v, mask=m, interpret=False,
                                     return_lse=True)
            return jnp.sum(o ** 2) + jnp.sum(jnp.where(lse > -1e29, lse, 0))

        qkv = ((2, 200, 4, 64), jnp.float32)
        _compile_v5e(jax.grad(loss, argnums=(0, 1, 2)), self._one(v5e),
                     qkv, qkv, qkv, ((2, 200), jnp.float32))

    def test_ring_flash_over_four_chips(self, v5e):
        # the same kernel under shard_map, against the four-device mesh
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from deeplearning4j_tpu.parallel.ring import SEQ_AXIS
        mesh = Mesh(np.array(v5e.devices), (SEQ_AXIS,))
        fn = _ring_flash(mesh)
        qkv = ((2, 2048, 4, 128), jnp.bfloat16)
        hlo = _compile_v5e(
            jax.grad(lambda q, k, v: jnp.sum(
                fn(q, k, v).astype(jnp.float32) ** 2), argnums=(0, 1, 2)),
            NamedSharding(mesh, P(None, SEQ_AXIS)), qkv, qkv, qkv)
        assert "flash_fwd" in hlo and "collective-permute" in hlo


class TestCompiledStepTable:
    """`monitor/xla.py::parse_hlo_ops` on programs as the chip's compiler
    prints them: tiled shapes, products written as convolutions inside
    fusions, a `lax.cond` whose instruction is called `cond.N`, custom
    calls (`tests/test_step_scopes.py` holds the grammar on hand-written
    text and on the CPU's programs)."""

    @staticmethod
    def _rows(v5e, fn, *avals):
        from jax.sharding import SingleDeviceSharding

        from deeplearning4j_tpu.monitor import xla
        one = SingleDeviceSharding(v5e.devices[0])
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one)
                for shape, dtype in avals]
        compiled = jax.jit(fn).lower(*args).compile()
        return xla.compiled_ops(compiled), compiled.as_text()

    def test_a_scan_of_checkpointed_layers_a_cond_and_a_grouped_conv(
            self, v5e):
        from deeplearning4j_tpu.monitor import scopes, xla

        def layer(w, x):
            with jax.named_scope("mlp/gated"):
                return jnp.tanh(x @ w)

        def f(ws, x, img, k, flag):
            def body(c, w):
                with scopes.layer_scope("blk"):
                    return jax.checkpoint(layer)(w, c), None
            y, _ = jax.lax.scan(body, x, ws)
            with scopes.layer_scope("stem"), jax.named_scope("conv"):
                z = jax.lax.conv_general_dilated(
                    img, k, (1, 1), "VALID",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    feature_group_count=4)
            with scopes.layer_scope("top"), jax.named_scope("merge"):
                y = jax.lax.cond(flag, lambda a: a @ a.T,
                                 lambda a: (a @ a.T) * 2, y)
            return jnp.sum(y.astype(jnp.float32)) \
                + jnp.sum(z.astype(jnp.float32))

        bf = jnp.bfloat16
        rows, text = self._rows(
            v5e, jax.grad(f, argnums=(0, 3)),
            ((3, 256, 256), bf), ((128, 256), bf), ((2, 18, 18, 32), bf),
            ((3, 3, 8, 64), bf), ((), jnp.bool_))
        by = {r["name"]: r for r in rows}
        assert "T(8,128)" in text and " dot(" not in text
        # the conditional is a container although XLA calls it `cond.N`
        conds = [r for r in rows if r["opcode"] == "conditional"]
        assert conds and all(r["name"].startswith("cond")
                             for r in conds)
        assert all(r["opcode"] in xla.CONTAINER_OPCODES for r in conds)
        whiles = [r for r in rows if r["opcode"] == "while"]
        assert len(whiles) == 2                   # forward, backward
        inside = lambda parent: [r for r in rows if r["parent"] == parent]
        # a branch's product names its conditional; (128x256)@(256x128)
        branch = [r for c in conds for r in inside(c["name"])
                  if r["dot_flops"]]
        assert branch and all(r["dot_flops"] % (2 * 128 * 128 * 256) == 0
                              and r["layer"] == "top"
                              and r["part"] == "merge" for r in branch)
        # a scan body's product names its while, sums its fusion's body:
        # (128x256)@(256x256) forward; again, marked, and transposed
        # twice in the backward scan
        one = 2 * 128 * 256 * 256
        fwd = [r for r in inside(whiles[0]["name"]) if r["dot_flops"]]
        bwd = [r for r in inside(whiles[1]["name"]) if r["dot_flops"]]
        if any(r["direction"] == "backward" for r in fwd):
            fwd, bwd = bwd, fwd
        assert [r["dot_flops"] for r in fwd] == [one]
        assert fwd[0]["opcode"] == "fusion" and not fwd[0]["recomputed"]
        assert (fwd[0]["layer"], fwd[0]["part"]) == ("blk", "mlp/gated")
        assert sum(r["dot_flops"] for r in bwd) == 3 * one
        assert any(r["recomputed"] for r in inside(bwd[0]["parent"]))
        assert all(r["direction"] == "backward" and r["layer"] == "blk"
                   for r in bwd)
        # the grouped convolution's gradient of its kernel, exact against
        # the shapes: 2 x (3x3x8x64 results) x (2x16x16 positions)
        stem = [r for r in rows if r["layer"] == "stem" and r["dot_flops"]]
        assert sum(r["dot_flops"] for r in stem) \
            == 2 * (3 * 3 * 8 * 64) * (2 * 16 * 16)
        # tiled shapes give their bytes
        assert fwd[0]["bytes_out"] == 128 * 256 * 2
        assert all(by[r["parent"]]["opcode"] in xla.CONTAINER_OPCODES
                   for r in rows if r["parent"])

    def test_a_pallas_call_and_a_grouped_product_hold_no_dot(self, v5e):
        from deeplearning4j_tpu.monitor import scopes, xla
        from deeplearning4j_tpu.nn.layers.attention import _grouped_matmul
        from deeplearning4j_tpu.ops.flash_attention import flash_attention

        def f(q, x, w, sizes):
            with scopes.layer_scope("blk"):
                with jax.named_scope("mha/attn"):
                    o = flash_attention(q, q, q, causal=True,
                                        interpret=False)
                with jax.named_scope("moe/experts"):
                    y = _grouped_matmul(x, w, sizes)
            return o, y

        bf = jnp.bfloat16
        rows, _ = self._rows(
            v5e, f, ((1, 1024, 4, 128), bf), ((1024, 256), bf),
            ((4, 256, 512), bf), ((4,), jnp.int32))
        calls = [r for r in rows if r["opcode"] == "custom-call"
                 and r["name"].split(".")[0] in ("flash_fwd",
                                                 "ragged-dot-none")]
        assert {r["name"].split(".")[0] for r in calls} \
            == {"flash_fwd", "ragged-dot-none"}
        assert all(r["dot_flops"] == 0 for r in calls)
        flash = next(r for r in calls if r["name"].startswith("flash"))
        assert (flash["layer"], flash["part"]) == ("blk", "mha/attn")
        # the kernel-call view counts the same instruction
        table = xla.OpTable(rows)
        assert xla.kernel_calls(table) == {"flash_fwd": 1}


class TestFlagshipLowering:
    def test_graft_entry_forward_lowers_for_tpu(self):
        # the driver compile-checks entry() on whatever chip it has;
        # this pins the TPU lowering of the same program at CI time.
        # No Pallas expected here — entry() is the plain-XLA flagship.
        import __graft_entry__ as ge
        fn, args = ge.entry()
        _export_tpu(fn, *args, expect_pallas=False)

    @pytest.mark.parametrize("s2d", [False, True])
    def test_resnet_train_step_lowers_for_tpu(self, s2d):
        # the bench's headline program at the REAL hardware spatial shape
        # (224x224 bf16) — a regression in the stem/device-norm/zoo that
        # only breaks TPU lowering must fail here, not on the chip
        import dataclasses

        import optax

        from deeplearning4j_tpu.models import ResNet50
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        model = ResNet50(num_classes=1000, input_shape=(224, 224, 3),
                         space_to_depth_stem=s2d)
        conf = dataclasses.replace(model.conf(),
                                   compute_dtype="bfloat16")
        net = ComputationGraph(conf).init()
        tx = net._tx
        x = jnp.zeros((8, 224, 224, 3), jnp.bfloat16)
        y = jnp.zeros((8, 1000), jnp.bfloat16)

        def step(params, opt_state, state, x, y, rng):
            def loss_fn(p):
                loss, (new_state, _) = net._score_fn(
                    p, state, (x,), (y,), None, None, True, rng)
                return loss, new_state
            (loss, new_state), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            updates, new_opt = tx.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), new_opt,
                    new_state, loss)

        _export_tpu(step, net.params, net.opt_state, net.state, x, y,
                    jax.random.PRNGKey(0), expect_pallas=False)


#: sha256 (first 16 hex digits) of the lowered StableHLO of each LM cell's
#: scan-of-2 step at its rehearsal sizes off the TPU, as the parent of the
#: PR that brought `HyperConnectedBlock` and YaRN lowers it: neither may
#: move a step that uses neither. A PR that means to change one of these
#: steps changes its line.
_LM_STEP_HASHES = {
    "kimi-linear-fit-8k-1chip": "7a5c7ed3b698bf07",
    "glm-4.7-flash-fit-8k-1chip": "748664be0295c4aa",
    "lfm2-24b-a2b-fit-8k-1chip": "4b70746bc0b0f908",
    "keye-vl-2.0-fit-32k-1chip": "21d08edfddba6a60",
    "sdar-30b-a3b-fit-8k-1chip": "7c6090cbc661a897",
    "nemotron-3-super-fit-8k-1chip": "51a48b79ffec2778",
}


@pytest.mark.parametrize("workload", sorted(_LM_STEP_HASHES))
def test_an_lm_cells_scan_step_lowers_as_its_parent_did(workload):
    import hashlib

    from benchmark.lib import manifest, train_cell
    cell = manifest.Cell(manifest.load_manifest(), workload).rehearsal()
    cfg, traffic = cell.config, cell.traffic
    ref = manifest.load_module("references", cell.config_name)
    system = manifest.load_module("systems", cfg["system"])
    net = system.build(cfg, ref.make_params(cfg, 1))
    pool = train_cell.make_batches(1, 2, int(traffic["batch"]), cfg)
    staged = net._stage_stacked(list(system.feed(pool, None))[:2])
    text = net._make_scan_step().lower(
        net.params, net.opt_state, net.state, *staged,
        jnp.zeros((2, 2), jnp.uint32)).as_text()
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == _LM_STEP_HASHES[workload]
