"""Chipless pre-flight: every Pallas kernel family compiles for a TPU v5e.

libtpu can describe a v5e topology with no chip attached, and
``jax.jit(f).lower(...).compile()`` against a device of that topology runs
the real Mosaic compiler. This is a COMPILE, not a run: it says nothing
about numerics, run-time memory or speed — ``chip_smoke.py`` and the
chip say those. It catches the kernel the compiler refuses before any chip
time is spent on it. Seconds per kernel; ``slow``-marked so the unmarked
tier stays compile-light.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from apex_tpu.utils import platform

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def v5e_chips():
    """The four devices of a 2x2 v5e host."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu, or one without v5e
        pytest.skip(f"libtpu cannot describe a v5e topology: {e!r}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return list(topo.devices)


@pytest.fixture(scope="module")
def v5e(v5e_chips):
    return v5e_chips[0]


@pytest.fixture(autouse=True)
def compile_for_tpu(monkeypatch):
    """Kernels choose interpret mode from the platform; the process runs
    on the CPU, the compile targets the TPU."""
    monkeypatch.setattr(platform, "_platform", lambda: "tpu")


MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'


def compile_text(device, fn, *shapes):
    """Compile ``fn`` for ``device`` at ``shapes`` ((shape, dtype) pairs);
    returns the compiled program's text."""
    sharding = SingleDeviceSharding(device)
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def compile_on(device, fn, *shapes):
    """The number of Mosaic custom calls in ``compile_text``'s program."""
    return compile_text(device, fn, *shapes).count(MOSAIC_CALL)


def mosaic_modules(lowered_text, kernel):
    """``(bytes, tpu.matmul operations, tpu.enqueue_dma operations)`` of
    each Mosaic module named ``kernel`` in a LOWERED program's text (the
    module rides in the custom call's ``backend_config`` as base64 of MLIR
    bytecode). Tracing a kernel and lowering Pallas to this module is work no
    compile cache saves a server's start (the cache's key is the lowered
    text), and every copy of a kernel's body, or of its descriptor code,
    shows here again."""
    import base64
    import re

    from jaxlib.mlir import ir

    found = []
    for body in re.findall(r'body\\22: \\22([A-Za-z0-9+/=]+)', lowered_text):
        raw = base64.b64decode(body)
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            asm = ir.Module.parse(raw).operation.get_asm()
        if kernel in asm:
            found.append((len(raw), asm.count("tpu.matmul"),
                          asm.count("tpu.enqueue_dma")))
    return found


# ``apex_mla_decode_fwd``'s set-up budget, which a server's every start pays
# in tracing, lowering and loading. Products: scores and values, in an
# unmasked and a masked body, and not once more (a ``switch`` over live
# lengths or an unrolled loop over blocks multiplies this number: PR 44's
# form held 16). Descriptor starts: a block's 16 pages straight-line in the
# loop and beside the last block, and one rolled up (PR 45's first form
# started chunks at six places: 30, and +2 s of every start). Bytes: the
# parent's module was 11 KB, this one is 21, PR 45's first form 33.
MLA_DECODE_MATMULS = 2 * 2
MLA_DECODE_STARTS = 2 * 16 + 1
MLA_DECODE_MODULE_BYTES = 24 << 10


def within_mla_budget(lowered_text):
    """The lowered program holds the kernel's module ONCE, however often it
    calls it (the call is a ``jit`` of its own: traced and lowered once a
    program), and inside the set-up budget."""
    modules = mosaic_modules(lowered_text, "apex_mla_decode_fwd")
    assert len(modules) == 1, modules
    ((size, matmuls, starts),) = modules
    assert 2 <= matmuls <= MLA_DECODE_MATMULS, modules
    assert starts <= MLA_DECODE_STARTS, modules
    assert size <= MLA_DECODE_MODULE_BYTES, modules


def check_mla_decode(device, *shapes):
    """``mla_decode_attention`` at ``shapes`` compiles for ``device`` to one
    Mosaic call whose lowered module keeps the set-up budget."""
    from apex_tpu.transformer.functional.mla_attention import (
        mla_decode_attention,
    )

    sharding = SingleDeviceSharding(device)
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    lowered = jax.jit(functools.partial(
        mla_decode_attention, value_width=512)).lower(*args)
    assert lowered.compile().as_text().count(MOSAIC_CALL) == 1
    within_mla_budget(lowered.as_text())


def _sum32(x):
    return jnp.sum(x.astype(jnp.float32))


@pytest.mark.parametrize("h", [1024, 4096])
@pytest.mark.parametrize("norm", ["layer", "rms"])
def test_norm_fwd_bwd(v5e, norm, h):
    from apex_tpu.normalization import (fused_layer_norm_affine,
                                        fused_rms_norm_affine)

    def f(x, w, b):
        if norm == "layer":
            return _sum32(fused_layer_norm_affine(x, w, b, h, 1e-5))
        return _sum32(fused_rms_norm_affine(x, w, h, 1e-5))

    assert compile_on(v5e, jax.grad(f, argnums=(0, 1, 2)),
                      ((8192, h), jnp.bfloat16), ((h,), jnp.float32),
                      ((h,), jnp.float32)) >= 2


@pytest.mark.parametrize("b,h,s,d,causal,dropout", [
    (64, 16, 128, 64, False, False), (64, 16, 128, 64, False, True),
    (8, 16, 1024, 64, True, False), (8, 16, 1024, 64, True, True),
    (4, 16, 2048, 64, True, False), (2, 16, 4096, 64, True, False),
    (4, 8, 2048, 128, True, False),
])
def test_flash_attention_fwd_bwd(v5e, b, h, s, d, causal, dropout):
    from apex_tpu.transformer.functional import flash_attention

    def f(q, k, v, mask):
        return _sum32(flash_attention(
            q, k, v, None if causal else mask, causal=causal,
            dropout_rate=0.1,
            dropout_rng=jax.random.PRNGKey(0) if dropout else None,
            use_kernel=True))

    qkv = ((b, h, s, d), jnp.bfloat16)
    assert compile_on(v5e, jax.grad(f, argnums=(0, 1, 2)), qkv, qkv, qkv,
                      ((b, s), jnp.int32)) >= 2


@pytest.mark.parametrize("b,s,h,d,dtype,dropout", [
    (64, 128, 16, 64, jnp.bfloat16, False),     # BERT-Large's projection
    (64, 128, 16, 64, jnp.bfloat16, True),
    (16, 256, 16, 64, jnp.bfloat16, False),
    (8, 256, 8, 128, jnp.float32, False),       # one row fills a step
    (13, 128, 2, 64, jnp.float32, False),       # rows by fours, ragged
])
def test_fmha_fwd_bwd(v5e, b, s, h, d, dtype, dropout):
    """The whole-sequence pair on the packed projection (``models/bert.py``'s
    call): one forward and ONE backward kernel, q, k and v thirds of ONE
    operand, the gradient ONE array of its shape, no score-shaped array and
    nothing transposed, sliced or concatenated around them."""
    from apex_tpu.transformer.functional import flash_attention_packed

    def f(qkv, mask):
        return _sum32(flash_attention_packed(
            qkv, mask, dropout_rate=0.1,
            dropout_rng=jax.random.PRNGKey(0) if dropout else None))

    text = compile_text(v5e, jax.grad(f), ((b, s, 3, h, d), dtype),
                        ((b, s), jnp.int32))
    assert text.count(MOSAIC_CALL) == 2
    assert "apex_fmha_fwd" in text and "apex_fmha_bwd" in text
    assert f"[{b},{h},{s},{s}]" not in text
    for op in (" transpose(", " concatenate(", " pad("):
        assert op not in text, op


def test_fused_softmax(v5e):
    from apex_tpu.transformer.functional import (
        scaled_masked_softmax, scaled_upper_triang_masked_softmax)

    x = ((8, 16, 1024, 1024), jnp.bfloat16)
    assert compile_on(v5e, lambda x, m: scaled_masked_softmax(x, m, 0.125),
                      x, ((8, 1, 1024, 1024), jnp.int32)) >= 1
    assert compile_on(
        v5e, lambda x: scaled_upper_triang_masked_softmax(x, 0.125), x) >= 1
    assert compile_on(
        v5e, jax.grad(lambda x: _sum32(
            scaled_upper_triang_masked_softmax(x, 0.125))), x) >= 2


@pytest.mark.parametrize("vocab,dtype", [(30522, jnp.float32),
                                         (50304, jnp.bfloat16)])
def test_xentropy_fwd_bwd(v5e, vocab, dtype):
    from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss
    from apex_tpu.utils.math import round_up_to_multiple

    text = compile_text(
        v5e, jax.grad(lambda x, y: jnp.sum(softmax_cross_entropy_loss(x, y))),
        ((8192, vocab), dtype), ((8192,), jnp.int32))
    assert text.count(MOSAIC_CALL) == 2
    # neither vocabulary is a multiple of the 2,048-lane block (30,720 and
    # 51,200 are the next ones): the kernels read and write the operand
    # itself, and nothing of the padded shape is in the program
    padded = round_up_to_multiple(vocab, 2048)
    assert padded != vocab and f"[8192,{vocab}]" in text
    assert f"[8192,{padded}]" not in text


@pytest.mark.parametrize("m,k,n", [
    (8, 1024, 1024), (64, 1024, 3072), (512, 1024, 4096), (8, 4096, 1024)])
def test_w8_matmul(v5e, m, k, n):
    from apex_tpu.quant.kernels import w8_matmul

    assert compile_on(v5e, w8_matmul, ((m, k), jnp.bfloat16),
                      ((k, n), jnp.int8), ((n,), jnp.float32),
                      ((n,), jnp.bfloat16)) == 1


@pytest.mark.parametrize("m", [8, 40])
def test_w8_matmul_nk(v5e, m):
    from apex_tpu.quant.kernels import w8_matmul_nk

    assert compile_on(v5e, w8_matmul_nk, ((m, 1024), jnp.bfloat16),
                      ((50304, 1024), jnp.int8),
                      ((50304,), jnp.float32)) == 1


@pytest.mark.parametrize("q_dtype,pool_dtype", [
    (jnp.bfloat16, jnp.bfloat16), (jnp.float32, jnp.bfloat16),
    (jnp.float32, jnp.float32)])
def test_paged_decode_attention(v5e, q_dtype, pool_dtype):
    """The served shape: 56 slots, 16 heads of 64, pages of 16, the whole
    24-layer pool of 3,586 pages handed over in HBM."""
    from apex_tpu.transformer.functional.paged_attention import (
        paged_decode_attention,
    )

    row = ((56, 1, 1024), q_dtype)
    pool = ((24, 3586, 16, 1024), pool_dtype)
    assert compile_on(
        v5e, functools.partial(paged_decode_attention, heads=16),
        row, row, row, pool, pool, ((56, 64), jnp.int32),
        ((56,), jnp.int32), ((), jnp.int32)) == 1


def test_paged_decode_attention_at_a_row_of_3840_lanes(v5e):
    """The hybrid configuration's full layers: 16 slots, 30 heads of 128 (a
    page row of 3,840 lanes, float32 queries), 4 layers of 4,098 pages."""
    from apex_tpu.transformer.functional.paged_attention import (
        paged_decode_attention,
    )

    pool = ((4, 4098, 16, 3840), jnp.bfloat16)
    new_row = ((16, 1, 3840), jnp.bfloat16)
    assert compile_on(
        v5e, functools.partial(paged_decode_attention, heads=30),
        ((16, 1, 3840), jnp.float32), new_row, new_row, pool, pool,
        ((16, 256), jnp.int32), ((16,), jnp.int32), ((), jnp.int32)) == 1


def test_drafter_decodes_through_the_paged_kernel(v5e):
    """The model drafter's per-token forward since PR 46: the paged decode
    step over the drafter's own pool under its identity table
    (``draft_gpt_medium``, 32 slots of 512 + 5 rows in pages of 16: heads
    of 64 as the target's), with ``apex_paged_decode_fwd`` in it."""
    from apex_tpu.models.gpt import draft_gpt_medium, init_gpt
    from apex_tpu.serving.decode import make_paged_decode_fn
    from apex_tpu.serving.draft_model import init_draft_cache

    cfg, slots = draft_gpt_medium(), 32
    sharding = SingleDeviceSharding(v5e)
    on = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)
    params = on(jax.eval_shape(
        lambda k: init_gpt(k, cfg, jnp.bfloat16), jax.random.PRNGKey(0)))
    cache = on(jax.eval_shape(functools.partial(
        init_draft_cache, cfg, slots, 512 + 5)))
    assert cache.block_tables.shape == (slots, 33)
    text = make_paged_decode_fn(cfg).lower(
        params, cache, on(jax.ShapeDtypeStruct((slots,), jnp.int32)),
        on(jax.ShapeDtypeStruct((slots,), jnp.bool_))).compile().as_text()
    assert "apex_paged_decode_fwd" in text


def test_gated_delta_kernels(v5e):
    """Both Gated DeltaNet kernels at the published widths (30 heads, d_k
    96, d_v 192): the chunked form over the largest bucket, and the step
    over the whole stacked state of 12 layers x 16 slots, aliased."""
    from apex_tpu.transformer.functional import gated_delta as gd

    f32 = jnp.float32
    assert compile_on(
        v5e, gd.gated_delta_chunked, ((30, 4096, 96), f32),
        ((30, 4096, 96), f32), ((30, 4096, 192), f32), ((30, 4096), f32),
        ((30, 4096), f32)) == 1
    sharding = SingleDeviceSharding(v5e)
    shapes = [((16, 30, 96), f32), ((16, 30, 96), f32), ((16, 30, 192), f32),
              ((16, 30), f32), ((16, 30), f32), ((12, 16, 30, 96, 192), f32),
              ((), jnp.int32), ((16,), jnp.bool_)]
    compiled = jax.jit(gd.gated_delta_step, donate_argnums=5).lower(
        *[jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    ).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
    # the state comes back in the buffer it came in: nothing its size is
    # allocated beside it
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 12 * 16 * 30 * 96 * 192 * 4
    assert mem.temp_size_in_bytes < 1 << 20


def test_hybrid_decode_runs_no_update_of_its_tails_twice(v5e):
    """``olmo_hybrid_7b.long_prompt_decode``'s decode program (4 of the 8
    periods, 16 slots, the full pool of 4096 positions a slot):
    ``models.hybrid`` shifts each layer's convolution tail by a row and
    writes it back over the donated buffer (``conv_step``), which is sound
    as long as the compiler runs no such update a second time from the
    buffer it has already written
    (``test_ling_decode_moves_no_row_of_its_tails``): the compiled program
    holds no rematerialised instruction."""
    from apex_tpu.models import hybrid
    from apex_tpu.serving.cache import init_hybrid_cache
    from apex_tpu.serving.decode import make_model_decode_fn

    slots, max_len, page = 16, 4096, 16
    cfg = dataclasses.replace(hybrid.olmo_hybrid_7b(), num_periods=4)
    sharding = SingleDeviceSharding(v5e)
    on = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)
    params = on(jax.eval_shape(
        lambda k: hybrid.init_hybrid(k, cfg, jnp.bfloat16),
        jax.random.PRNGKey(0)))
    cache = on(jax.eval_shape(functools.partial(
        init_hybrid_cache, cfg, slots, max_len,
        slots * (max_len // page) + 2, page, jnp.bfloat16)))
    assert cache.conv.shape == (12, slots, 3, 11520)
    text = make_model_decode_fn(cfg).lower(
        params, cache, on(jax.ShapeDtypeStruct((slots,), jnp.int32)),
        on(jax.ShapeDtypeStruct((slots,), jnp.bool_))).compile().as_text()
    assert "apex_gdn_decode_fwd" in text and ".remat" not in text


def test_nemotron_kernels(v5e):
    """The two kernels of the ``nemotron_h`` family at the published widths:
    the Mamba-2 step over the whole stacked state of 5 layers x 128 slots
    (128 heads of 64 x 128), aliased; the grouped expert product over 128
    held experts, both products, at a decode tick's rows and at the largest
    bucket's; and the paged kernel at 32 query heads over 2 K/V heads."""
    from apex_tpu.transformer.functional import moe
    from apex_tpu.transformer.functional.paged_attention import (
        paged_decode_attention,
    )
    from apex_tpu.transformer.functional.ssd import ssd_step

    f32, bf16 = jnp.float32, jnp.bfloat16
    sharding = SingleDeviceSharding(v5e)
    shapes = [((128, 128, 64), f32), ((128, 128), f32), ((128,), f32),
              ((128, 8, 128), f32), ((128, 8, 128), f32),
              ((5, 128, 128, 64, 128), f32), ((), jnp.int32),
              ((128,), jnp.bool_)]
    compiled = jax.jit(ssd_step, donate_argnums=5).lower(
        *[jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    ).compile()
    assert compiled.as_text().count(MOSAIC_CALL) == 1
    # the 2.7 GB state comes back in the buffer it came in
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 5 * 128 * 128 * 64 * 128 * 4
    assert mem.temp_size_in_bytes < 1 << 20
    for rows in (128 * 22, 1024 * 22):
        assert compile_on(
            v5e, functools.partial(moe.grouped_matmul, activation="relu2",
                                   out_dtype=bf16),
            ((rows, 1024), bf16), ((128, 1024, 2688), bf16),
            ((128,), jnp.int32)) == 1
        assert compile_on(
            v5e, moe.grouped_matmul, ((rows, 2688), bf16),
            ((128, 2688, 1024), bf16), ((128,), jnp.int32)) == 1
    pool = ((1, 8194, 16, 256), bf16)
    new_row = ((128, 1, 256), bf16)
    assert compile_on(
        v5e, functools.partial(paged_decode_attention, heads=32, kv_heads=2),
        ((128, 1, 4096), f32), new_row, new_row, pool, pool,
        ((128, 64), jnp.int32), ((128,), jnp.int32), ((), jnp.int32)) == 1


def test_nemotron_full_size_programs(v5e):
    """The two programs of ``nemotron3_super_120b_a12b.many_slot_decode`` at
    full size (one period ``MEMEMEMEM*E``, 128 of 512 experts, a quarter of
    the vocabulary, 128 slots, the full pool): both compile for a v5e with no
    chip; ``memory_analysis`` gives what the configuration file says (9.30 GB
    of weights, 2.90 GB of cache, all of it aliased) and fits the chip; the
    kernel names are the engagement counters the trace readers count."""
    import re

    from apex_tpu.models import nemotron_h as nh
    from apex_tpu.serving.cache import init_hybrid_cache
    from apex_tpu.serving.decode import (
        make_model_decode_fn, make_model_prefill_fn,
    )

    slots, bucket = 128, 512
    cfg = nh.NemotronHConfig(vocab_size=32768, pattern="MEMEMEMEM*E",
                             experts_held=128, max_position_embeddings=1024)
    sharding = SingleDeviceSharding(v5e)
    on = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)
    params = on(jax.eval_shape(lambda k: nh.init(k, cfg, jnp.bfloat16),
                               jax.random.PRNGKey(0)))
    cache = on(jax.eval_shape(functools.partial(
        init_hybrid_cache, cfg, slots, 1024, slots * 64 + 2, 16,
        jnp.bfloat16)))
    size = lambda tree: sum(a.size * a.dtype.itemsize
                            for a in jax.tree.leaves(tree))
    assert round(size(params) / 1e9, 2) == 9.30
    assert round(size(cache) / 1e9, 2) == 2.90
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=sharding)
    i32 = jnp.int32
    programs = {
        "decode": make_model_decode_fn(cfg).lower(
            params, cache, sds((slots,), i32), sds((slots,), jnp.bool_)),
        "prefill": make_model_prefill_fn(cfg).lower(
            params, cache, sds((1, bucket), i32), sds((bucket,), i32),
            sds((), i32), sds((bucket // 16,), i32), sds((64,), i32))}
    want = {"decode": {"apex_ssd_decode_fwd": 5, "apex_moe_gmm_fwd": 10,
                       "apex_paged_decode_fwd": 1, "apex_flash_fwd": 0},
            "prefill": {"apex_ssd_decode_fwd": 0, "apex_moe_gmm_fwd": 10,
                        "apex_paged_decode_fwd": 0, "apex_flash_fwd": 1}}
    for name, lowered in programs.items():
        compiled = lowered.compile()
        text = compiled.as_text()
        got = {k: len(re.findall(rf"%{k}(\.\d+)? = ", text))
               for k in want[name]}
        assert got == want[name], name
        # the tails are shifted by a row and written back (``conv_step``):
        # sound as long as no in-place update of them is run a second time
        # (``test_ling_decode_moves_no_row_of_its_tails``)
        assert ".remat" not in text, name
        mem = compiled.memory_analysis()
        # the donated cache comes back in place: nothing its size beside it
        # (padded to tiles: a few KB over the arrays' own bytes)
        assert 0 <= mem.alias_size_in_bytes - size(cache) < 1 << 16, name
        assert mem.temp_size_in_bytes < 0.5e9, name
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            < 0.85 * 16 * 2 ** 30, name


def test_deepseek_kernels(v5e):
    """``apex_mla_decode_fwd`` at the cell's shapes (128 absorbed queries of
    640 a slot, 64 slots, the full pool of 25602 pages of 16 rows over 5
    layers, 400 pages a slot), and the grouped product at the SwiGLU expert's
    two shapes (the fused gate and up matrix at hidden 7168, where a column
    tile is 256 wide), for a decode tick's rows and a prompt block's."""
    from apex_tpu.transformer.functional import moe

    f32, bf16 = jnp.float32, jnp.bfloat16
    check_mla_decode(
        v5e, ((64, 128, 640), f32), ((64, 640), f32),
        ((5, 25602, 16, 640), bf16), ((64, 400), jnp.int32),
        ((64,), jnp.int32), ((), jnp.int32))
    assert moe._column_tile(7168, 4096, 2) == 256       # 3.7 MB a tile
    assert moe._column_tile(2048, 7168, 2) == 512
    # a scanned layer's experts, read in place out of the four layers' stack
    gmm = lambda lhs, rhs, sizes, first: moe.grouped_matmul(
        lhs, rhs, sizes, first_group=first)
    for rows in (64 * 8, 1024 * 8):
        assert compile_on(
            v5e, gmm, ((rows, 7168), f32), ((64, 7168, 4096), bf16),
            ((16,), jnp.int32), ((), jnp.int32)) == 1
        assert compile_on(
            v5e, gmm, ((rows, 2048), f32), ((64, 2048, 7168), bf16),
            ((16,), jnp.int32), ((), jnp.int32)) == 1


def test_deepseek_full_size_programs(v5e):
    """The programs of ``deepseek_v3.resident_context_decode`` at full size
    (one leading dense layer and 4 expert layers, 16 of 256 experts, an
    eighth of the vocabulary, 64 slots, the full pool of 6400 positions a
    slot): decode and every prefill bucket compile for a v5e with no chip;
    ``memory_analysis`` gives what the configuration file says (9.13 GB of
    weights, 2.62 GB of cache in ONE pool, all of it aliased) and fits the
    chip; the kernel names are the engagement counters the trace readers
    count (``apex_mla_decode_fwd`` once a layer in decode and never in a
    prefill, which expands)."""
    import re

    from apex_tpu.models import deepseek
    from apex_tpu.serving.cache import init_latent_cache
    from apex_tpu.serving.decode import (
        make_model_decode_fn, make_model_prefill_fn,
    )

    slots, max_len, page = 64, 6400, 16
    cfg = deepseek.DeepseekConfig(vocab_size=16160, num_layers=5,
                                  first_k_dense=1, experts_held=16)
    sharding = SingleDeviceSharding(v5e)
    on = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)
    params = on(jax.eval_shape(lambda k: deepseek.init(k, cfg, jnp.bfloat16),
                               jax.random.PRNGKey(0)))
    cache = on(jax.eval_shape(functools.partial(
        init_latent_cache, cfg, slots, max_len,
        slots * (max_len // page) + 2, page, jnp.bfloat16)))
    size = lambda tree: sum(a.size * a.dtype.itemsize
                            for a in jax.tree.leaves(tree))
    assert round(size(params) / 1e9, 2) == 9.13
    assert round(size(cache) / 1e9, 2) == 2.62
    assert cache.k.shape == (5, 25602, 16, 640) and cache.v is None
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=sharding)
    i32 = jnp.int32
    programs = {"decode": make_model_decode_fn(cfg).lower(
        params, cache, sds((slots,), i32), sds((slots,), jnp.bool_))}
    for bucket in (1024, 2048, 4096):
        programs[f"prefill_{bucket}"] = make_model_prefill_fn(cfg).lower(
            params, cache, sds((1, bucket), i32), sds((bucket,), i32),
            sds((), i32), sds((bucket // page,), i32),
            sds((max_len // page,), i32))
    # the set-up budget: the dense scan's call and the expert scan's are one
    # function of the lowered module (the compiled program holds both: below)
    within_mla_budget(programs["decode"].as_text())
    # a scanned layer's kernels stand once in the program's text
    want = {"decode": {"apex_mla_decode_fwd": 2, "apex_moe_gmm_fwd": 2,
                       "apex_flash_fwd": 0},
            "prefill": {"apex_mla_decode_fwd": 0, "apex_moe_gmm_fwd": 2,
                        "apex_flash_fwd": 2}}
    for name, lowered in programs.items():
        compiled = lowered.compile()
        text = compiled.as_text()
        kind = name.split("_")[0]
        got = {k: len(re.findall(rf"%{k}(\.\d+)? = ", text))
               for k in want[kind]}
        assert got == want[kind], name
        mem = compiled.memory_analysis()
        assert 0 <= mem.alias_size_in_bytes - size(cache) < 1 << 16, name
        print(name, mem.temp_size_in_bytes / 1e9,
              (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 2 ** 30)
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            < 0.96 * 16 * 2 ** 30, name


def test_exaone_kernels(v5e):
    """The paged decode kernel at the ``k_exaone_236b_a23b`` cell's two calls
    (64 query heads over 8 K/V heads of 128, 64 slots): the full layer's over
    the pool of 45570 pages and 712 a slot, and the sliding layers' BOUNDED
    call (``start``) over the 578 pages of the window pool through the (64,
    9) view of the cyclic table; the prompt path's flash attention (float32,
    ``precision=HIGHEST``) at the three prefill buckets, banded (2 k tiles a
    q tile, whatever the prompt) and full; the grouped
    product at the SwiGLU expert's two shapes at hidden 6144."""
    from apex_tpu.transformer.functional import flash_attention, moe
    from apex_tpu.transformer.functional.flash_attention import _band_tiles
    from apex_tpu.transformer.functional.paged_attention import (
        paged_decode_attention,
    )

    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    paged = functools.partial(paged_decode_attention, heads=64, kv_heads=8)
    row = ((64, 1, 1024), bf16)
    assert compile_on(
        v5e, paged, ((64, 1, 8192), f32), row, row,
        ((1, 45570, 16, 1024), bf16), ((1, 45570, 16, 1024), bf16),
        ((64, 712), i32), ((64,), i32), ((), i32)) == 1
    bounded = lambda q, k, v, kp, vp, bt, pos, layer, start: paged(
        q, k, v, kp, vp, bt, pos, layer, start=start)
    assert compile_on(
        v5e, bounded, ((64, 1, 8192), f32), row, row,
        ((4, 578, 16, 1024), bf16), ((4, 578, 16, 1024), bf16),
        ((64, 9), i32), ((64,), i32), ((), i32), ((64,), i32)) == 1
    # the prompt path's two calls: float32 operands, both products at the
    # MXU's full precision, banded (the sliding layers) and not (the full)
    exact = functools.partial(flash_attention, causal=True,
                              precision=jax.lax.Precision.HIGHEST)
    for s in (2048, 4096, 8192):
        assert _band_tiles(512, 512, 128, s // 512) == 2
        qkv = ((1, 64, s, 128), f32)
        for window in (128, None):
            assert compile_on(v5e, functools.partial(exact, window=window),
                              qkv, qkv, qkv, ((1, s), i32)) == 1
    assert moe._column_tile(6144, 4096, 2) == 256       # 3.1 MB a tile
    gmm = lambda lhs, rhs, sizes, first: moe.grouped_matmul(
        lhs, rhs, sizes, first_group=first)
    for rows in (64 * 8, 1024 * 8):
        assert compile_on(
            v5e, gmm, ((rows, 6144), f32), ((64, 6144, 4096), bf16),
            ((16,), i32), ((), i32)) == 1
        assert compile_on(
            v5e, gmm, ((rows, 2048), f32), ((64, 2048, 6144), bf16),
            ((16,), i32), ((), i32)) == 1


def test_exaone_full_size_programs(v5e):
    """The programs of ``k_exaone_236b_a23b.long_context_reasoning`` at full
    size (one leading dense layer and 4 expert layers, 16 of 128 experts, an
    eighth of the vocabulary, 64 slots, the full layer's pool of 11392
    positions a slot and the four sliding layers' 9 pages a slot): decode and
    every prefill bucket compile for a v5e with no chip; ``memory_analysis``
    gives what the configuration file says (7.42 GB of weights, 2.99 GB in
    the full pool and 0.15 GB in the window pool, all of it aliased) and fits
    the chip; the kernel names are the engagement counters the trace readers
    count (the full layer's call and the sliding layers' under their two
    names, in decode only; the prompt path runs flash attention, banded in
    the sliding layers)."""
    import re

    from apex_tpu.models import exaone_moe
    from apex_tpu.serving.cache import init_window_cache
    from apex_tpu.serving.decode import (
        make_model_decode_fn, make_model_prefill_fn,
    )

    slots, max_len, page = 64, 11392, 16
    kinds = exaone_moe.ExaoneMoeConfig().layer_types[:5]
    cfg = exaone_moe.ExaoneMoeConfig(vocab_size=19200, num_layers=5,
                                     layer_types=kinds, experts_held=16)
    sharding = SingleDeviceSharding(v5e)
    on = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)
    params = on(jax.eval_shape(
        lambda k: exaone_moe.init(k, cfg, jnp.bfloat16),
        jax.random.PRNGKey(0)))
    cache = on(jax.eval_shape(functools.partial(
        init_window_cache, cfg, slots, max_len,
        slots * (max_len // page) + 2, page, jnp.bfloat16)))
    size = lambda tree: sum(a.size * a.dtype.itemsize
                            for a in jax.tree.leaves(tree))
    assert round(size(params) / 1e9, 2) == 7.42
    assert cache.k.shape == (1, 45570, 16, 1024)
    assert cache.wk.shape == (4, 578, 16, 1024)
    assert round(size((cache.k, cache.v)) / 1e9, 2) == 2.99
    assert round(size((cache.wk, cache.wv)) / 1e9, 2) == 0.15
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=sharding)
    i32 = jnp.int32
    programs = {"decode": make_model_decode_fn(cfg).lower(
        params, cache, sds((slots,), i32), sds((slots,), jnp.bool_))}
    for bucket in (2048, 4096, 8192):
        programs[f"prefill_{bucket}"] = make_model_prefill_fn(cfg).lower(
            params, cache, sds((1, bucket), i32), sds((bucket,), i32),
            sds((), i32), sds((bucket // page,), i32),
            sds((max_len // page,), i32))
    # the dense layer's kernels stand unrolled, the scanned period's once
    # each call site: sliding, sliding, full, sliding
    want = {"decode": {"apex_paged_decode_fwd": 1,
                       "apex_paged_window_decode_fwd": 4,
                       "apex_moe_gmm_fwd": 8, "apex_flash_fwd": 0},
            "prefill": {"apex_paged_decode_fwd": 0,
                        "apex_paged_window_decode_fwd": 0,
                        "apex_moe_gmm_fwd": 8, "apex_flash_fwd": 5}}
    for name, lowered in programs.items():
        compiled = lowered.compile()
        text = compiled.as_text()
        kind = name.split("_")[0]
        got = {k: len(re.findall(rf"%{k}(\.\d+)? = ", text))
               for k in want[kind]}
        assert got == want[kind], (name, got)
        mem = compiled.memory_analysis()
        assert 0 <= mem.alias_size_in_bytes - size(cache) < 1 << 16, name
        print(name, mem.temp_size_in_bytes / 1e9,
              (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 2 ** 30)
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            < 0.96 * 16 * 2 ** 30, name


def test_ling_kernels(v5e):
    """Both Kimi-Delta-Attention kernels at the ``ling3_flash_vl`` cell's
    shapes (32 heads of 128, a decay per key channel): the chunked form over
    the largest bucket and the step over the whole stacked state of 6 layers
    x 256 slots, aliased; ``apex_mla_decode_fwd`` at 32 absorbed queries a
    slot against the ONE layer's pool of 131074 pages, 512 a slot; and the
    grouped product at the expert's two shapes (hidden 2560, experts of 768)
    for a decode tick's rows and a prompt block's."""
    from apex_tpu.transformer.functional import gated_delta as gd
    from apex_tpu.transformer.functional import moe

    f32, bf16 = jnp.float32, jnp.bfloat16
    text = compile_text(
        v5e, gd.gated_delta_chunked, ((32, 4096, 128), f32),
        ((32, 4096, 128), f32), ((32, 4096, 128), f32),
        ((32, 4096, 128), f32), ((32, 4096), f32))
    assert text.count(MOSAIC_CALL) == 1 and "apex_kda_chunk_fwd" in text
    sharding = SingleDeviceSharding(v5e)
    shapes = [((256, 32, 128), f32), ((256, 32, 128), f32),
              ((256, 32, 128), f32), ((256, 32, 128), f32), ((256, 32), f32),
              ((6, 256, 32, 128, 128), f32), ((), jnp.int32),
              ((256,), jnp.bool_)]
    compiled = jax.jit(gd.gated_delta_step, donate_argnums=5).lower(
        *[jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    ).compile()
    text = compiled.as_text()
    assert text.count(MOSAIC_CALL) == 1 and "apex_kda_decode_fwd" in text
    # the state comes back in the buffer it came in: nothing its size is
    # allocated beside it
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 6 * 256 * 32 * 128 * 128 * 4
    assert mem.temp_size_in_bytes < 64 << 20
    check_mla_decode(
        v5e, ((256, 32, 640), f32), ((256, 640), f32),
        ((1, 131074, 16, 640), bf16), ((256, 512), jnp.int32),
        ((256,), jnp.int32), ((), jnp.int32))
    assert moe._column_tile(2560, 1536, 2) == 512       # 2.6 MB a tile
    assert moe._column_tile(768, 2560, 2) == 1280
    for rows in (256 * 8, 1024 * 8):
        assert compile_on(
            v5e, moe.grouped_matmul, ((rows, 2560), f32),
            ((64, 2560, 1536), bf16), ((64,), jnp.int32)) == 1
        assert compile_on(
            v5e, moe.grouped_matmul, ((rows, 768), f32),
            ((64, 768, 2560), bf16), ((64,), jnp.int32)) == 1


LING_SLOTS = 256


def ling_full_size(v5e):
    """``(cfg, params, cache, sds)`` of the ``ling3_flash_vl`` cell as shapes
    on ``v5e``: layer 1 dense and layers 2-7 sparse, 64 of 512 experts, an
    eighth of the vocabulary, 256 slots, the full pool."""
    from apex_tpu.models import bailing_hybrid as bh
    from apex_tpu.serving.cache import init_hybrid_cache

    slots, max_len, page = LING_SLOTS, 8192, 16
    cfg = bh.BailingHybridConfig(
        vocab_size=19648, layer_types=bh.layer_types_of(1, 7, 6),
        first_k_dense=1, experts_held=64)
    sharding = SingleDeviceSharding(v5e)
    on = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)
    params = on(jax.eval_shape(lambda k: bh.init(k, cfg, jnp.bfloat16),
                               jax.random.PRNGKey(0)))
    cache = on(jax.eval_shape(functools.partial(
        init_hybrid_cache, cfg, slots, max_len,
        slots * (max_len // page) + 2, page, jnp.bfloat16)))
    return cfg, params, cache, lambda s, d: jax.ShapeDtypeStruct(
        s, d, sharding=sharding)


def test_ling_full_size_programs(v5e):
    """The programs of ``ling3_flash_vl.many_stream_reasoning`` at full size
    (layer 1 dense and layers 2-7 sparse, ``K | K K K M K K``; 64 of 512
    experts, an eighth of the vocabulary, 256 slots, the full pool of 8192
    positions a slot): decode and every prefill bucket compile for a v5e with
    no chip; ``memory_analysis`` gives what the configuration file says (5.73
    GB of weights; 3.22 GB of state, the tails and 2.68 GB of latents in ONE
    pool, all of it aliased: 5 leaves and 3 counters) and fits the chip; the
    kernel names are the engagement counters the trace readers count."""
    import re

    from apex_tpu.serving.decode import (
        make_model_decode_fn, make_model_prefill_fn,
    )

    slots, max_len, page = LING_SLOTS, 8192, 16
    cfg, params, cache, sds = ling_full_size(v5e)
    size = lambda tree: sum(a.size * a.dtype.itemsize
                            for a in jax.tree.leaves(tree))
    matrices = sum(a.size for a in jax.tree.leaves(params)
                   if a.dtype == jnp.bfloat16 and a.ndim >= 2) \
        - 6 * 4 * 12288                     # the convolutions' taps
    assert matrices == 2_865_905_664
    assert round(size(params) / 1e9, 2) == 5.73
    assert cache.v is None and cache.k.shape == (1, 131074, 16, 640)
    assert cache.state.shape == (6, slots, 32, 128, 128)
    assert cache.conv.shape == (6, slots, 3, 12288)
    assert round(cache.state.size * 4 / 1e9, 2) == 3.22
    assert round(cache.k.size * 2 / 1e9, 2) == 2.68
    assert len(jax.tree.leaves(cache)) == 5 + 3
    i32 = jnp.int32
    programs = {"decode": make_model_decode_fn(cfg).lower(
        params, cache, sds((slots,), i32), sds((slots,), jnp.bool_))}
    for bucket in (1024, 2048, 4096):
        programs[f"prefill_{bucket}"] = make_model_prefill_fn(cfg).lower(
            params, cache, sds((1, bucket), i32), sds((bucket,), i32),
            sds((), i32), sds((bucket // page,), i32),
            sds((max_len // page,), i32))
    within_mla_budget(programs["decode"].as_text())
    # the layers are unrolled: every call stands in the program's text
    want = {"decode": {"apex_kda_decode_fwd": 6, "apex_kda_chunk_fwd": 0,
                       "apex_mla_decode_fwd": 1, "apex_moe_gmm_fwd": 12,
                       "apex_flash_fwd": 0, "apex_gdn_decode_fwd": 0},
            "prefill": {"apex_kda_decode_fwd": 0, "apex_kda_chunk_fwd": 6,
                        "apex_mla_decode_fwd": 0, "apex_moe_gmm_fwd": 12,
                        "apex_flash_fwd": 1, "apex_gdn_chunk_fwd": 0}}
    for name, lowered in programs.items():
        compiled = lowered.compile()
        text = compiled.as_text()
        kind = name.split("_")[0]
        got = {k: len(re.findall(rf"%{k}(\.\d+)? = ", text))
               for k in want[kind]}
        assert got == want[kind], name
        mem = compiled.memory_analysis()
        print(name, "args", mem.argument_size_in_bytes / 1e9, "temp",
              mem.temp_size_in_bytes / 1e9, "alias",
              mem.alias_size_in_bytes / 1e9, "cache", size(cache) / 1e9,
              (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 2 ** 30)
        assert mem.alias_size_in_bytes >= size(cache), name
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            < 0.96 * 16 * 2 ** 30, name


def moved_rows(text, shape):
    """Of the compiled program ``text``, the lines of every fusion that
    writes a buffer of ``shape`` (its root a ``dynamic-update-slice`` of one)
    which MOVE data within it: a pad, a concatenation, or a slice that does
    not start at 0 and span the whole of every dimension after the first.
    Such a fusion reads a row where it does not write it: written in place,
    or run twice, it can read what it has already overwritten."""
    import re

    said = "f32[" + ",".join(map(str, shape)) + "]"
    found, body = [], []
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            body = []           # a computation's header
        body.append(line)
        if line.startswith("}") and any(
                l.lstrip().startswith("ROOT") and "dynamic-update-slice("
                in l and f"= {said}" in l for l in body):
            for l in body:
                whole = all(
                    (int(a), int(b)) == (0, n) for (a, b), n in zip(
                        re.findall(r"\[(\d+):(\d+)\]", l)[1 - len(shape):],
                        shape[1:]))
                if " pad(" in l or " concatenate(" in l \
                        or (" slice(" in l and not whole):
                    found.append(l.strip()[:160])
    return found


def test_ling_decode_moves_no_row_of_its_tails(v5e, monkeypatch):
    """The convolution tails are donated and updated in place, a layer at a
    time, and at this size the compiler REMATERIALISES the first layer's
    update: it runs it twice, the second time from the buffer the first has
    already written. The ring update (``conv_ring_step``) is elementwise in
    the tail, so neither the in-place write nor the second run can change
    what it reads. The tail SHIFTED by a row and written back
    (``conv_step``, as the first full-size run of the cell had it: NOT
    ``correct``, the first KDA layer's tail shifted twice; my chip run, PR
    42) is what this check refuses."""
    from jax import lax

    from apex_tpu.models import bailing_hybrid as bh
    from apex_tpu.serving.decode import make_model_decode_fn
    from apex_tpu.transformer.functional.gated_delta import (
        conv_step, gated_delta_step,
    )

    cfg, params, cache, sds = ling_full_size(v5e)
    args = (params, cache, sds((LING_SLOTS,), jnp.int32),
            sds((LING_SLOTS,), jnp.bool_))
    text = make_model_decode_fn(cfg).lower(*args).compile().as_text()
    assert moved_rows(text, cache.conv.shape) == []

    def shifted(lp, x, cfg, state, conv, layer, pos, active):
        conv_in, log_decay, gate, beta = bh._kda_in(lp, x, cfg)
        tail = lax.dynamic_index_in_dim(conv, layer, 0, keepdims=False)
        conv_out, new = conv_step(
            conv_in, tail, lp["conv"]["weight"].astype(jnp.float32))
        conv = lax.dynamic_update_index_in_dim(
            conv, jnp.where(active[:, None, None], new, tail), layer, 0)
        q, k, v = bh._kda_heads(conv_out, cfg)
        o, state = gated_delta_step(q, k, v, log_decay, beta, state,
                                    jnp.int32(layer), active)
        return x + bh._kda_out(lp, o, gate, cfg), state, conv

    monkeypatch.setattr(bh, "kda_decode", shifted)
    text = make_model_decode_fn(cfg).lower(*args).compile().as_text()
    assert len(moved_rows(text, cache.conv.shape)) >= 6
    said = "f32[" + ",".join(map(str, cache.conv.shape)) + "]"
    assert any(".remat" in l.split(" = ")[0] and f"= {said}" in l
               for l in text.splitlines())


def test_glm_kernels(v5e):
    """The kernels at the ``glm_5_3_flash`` cell's shapes: both KDA kernels at
    64 heads of 128 (the step over the stacked state of 4 layers x 64 slots,
    aliased); ``apex_dsa_index_fwd`` at 32 index heads against the ONE sparse
    layer's cache of 65538 pages of 4 pooled keys, 1024 pages a slot;
    ``apex_mla_decode_fwd`` at 64 absorbed queries a slot against a 512-wide
    row with no roped part (key width = value width), over the gathered
    buffer of 129 pages a slot under its identity table; and the grouped
    product at the expert's two shapes (hidden 4096, experts of 2048) for a
    decode tick's rows and a prompt block's."""
    from apex_tpu.transformer.functional import gated_delta as gd
    from apex_tpu.transformer.functional import moe, sparse_index

    f32, bf16 = jnp.float32, jnp.bfloat16
    text = compile_text(
        v5e, gd.gated_delta_chunked, ((64, 4096, 128), f32),
        ((64, 4096, 128), f32), ((64, 4096, 128), f32),
        ((64, 4096, 128), f32), ((64, 4096), f32))
    assert text.count(MOSAIC_CALL) == 1 and "apex_kda_chunk_fwd" in text
    sharding = SingleDeviceSharding(v5e)
    shapes = [((64, 64, 128), f32), ((64, 64, 128), f32),
              ((64, 64, 128), f32), ((64, 64, 128), f32), ((64, 64), f32),
              ((4, 64, 64, 128, 128), f32), ((), jnp.int32),
              ((64,), jnp.bool_)]
    compiled = jax.jit(gd.gated_delta_step, donate_argnums=5).lower(
        *[jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    ).compile()
    assert compiled.as_text().count(MOSAIC_CALL) == 1
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 4 * 64 * 64 * 128 * 128 * 4
    text = compile_text(
        v5e, sparse_index.index_scores, ((64, 32, 128), f32), ((64, 32), f32),
        ((1, 65538, 4, 128), bf16), ((64, 1024), jnp.int32),
        ((64,), jnp.int32), ((), jnp.int32))
    assert text.count(MOSAIC_CALL) == 1 and "apex_dsa_index_fwd" in text
    # the cache stands in HBM as it is kept: no padded copy of it is made
    assert "bf16[1,65538,4,128]{3,2,1,0:T(4,128)(2,1)}" in text
    check_mla_decode(
        v5e, ((64, 64, 512), f32), ((64, 512), f32),
        ((1, 64 * 129, 16, 512), bf16), ((64, 129), jnp.int32),
        ((64,), jnp.int32), ((), jnp.int32))
    for rows in (64 * 8, 1024 * 8):
        assert compile_on(
            v5e, moe.grouped_matmul, ((rows, 4096), f32),
            ((36, 4096, 4096), bf16), ((36,), jnp.int32)) == 1
        assert compile_on(
            v5e, moe.grouped_matmul, ((rows, 2048), f32),
            ((36, 2048, 4096), bf16), ((36,), jnp.int32)) == 1


GLM_SLOTS = 64


def glm_full_size(v5e):
    """``(cfg, params, cache, sds)`` of the ``glm_5_3_flash`` cell as shapes on
    ``v5e``: layer 2 dense and layers 3-6 sparse, 36 of 288 experts, an
    eighth of the vocabulary, 64 slots, the full pool of 16384 positions a
    slot."""
    from apex_tpu.models import glm_next
    from apex_tpu.serving.cache import init_hybrid_cache

    slots, max_len, page = GLM_SLOTS, 16384, 16
    cfg = glm_next.GlmNextConfig(
        vocab_size=19360, layer_types=glm_next.layer_types_of(2, 5),
        first_k_dense=1, experts_held=36)
    sharding = SingleDeviceSharding(v5e)
    on = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)
    params = on(jax.eval_shape(lambda k: glm_next.init(k, cfg, jnp.bfloat16),
                               jax.random.PRNGKey(0)))
    cache = on(jax.eval_shape(functools.partial(
        init_hybrid_cache, cfg, slots, max_len,
        slots * (max_len // page) + 2, page, jnp.bfloat16)))
    return cfg, params, cache, lambda s, d: jax.ShapeDtypeStruct(
        s, d, sharding=sharding)


def test_glm_full_size_programs(v5e):
    """The programs of ``glm_5_3_flash.long_resident_sparse_decode`` at full
    size (layer 2 dense and layers 3-6 sparse, ``K | D K K K``; 36 of 288
    experts, an eighth of the vocabulary, 64 slots, the full pool of 16384
    positions a slot): decode and the prefill bucket compile for a v5e with
    no chip; ``memory_analysis`` gives what the configuration file says (9.44
    GB of weights; 1.07 GB of state, 1.07 GB of latents in ONE pool and 0.07
    GB of index keys beside it, all of it aliased: 5 leaves, the index's 2
    and 5 counters) and fits the chip; the kernel names are the engagement
    counters the trace readers count. The two in-place writes this model
    adds (the index keys' scatter and the index tail's ring) move no row
    within their buffers, so that the compiler may run them twice
    (``test_ling_decode_moves_no_row_of_its_tails``)."""
    import re

    from apex_tpu.serving.decode import (
        make_model_decode_fn, make_model_prefill_fn,
    )

    slots, max_len, page = GLM_SLOTS, 16384, 16
    cfg, params, cache, sds = glm_full_size(v5e)
    size = lambda tree: sum(a.size * a.dtype.itemsize
                            for a in jax.tree.leaves(tree))
    matrices = sum(a.size for a in jax.tree.leaves(params)
                   if a.ndim >= 2 and a.shape[-1] > 4) \
        - 4 * 4 * 24576                     # the convolutions' taps
    assert matrices == 4_717_674_496
    assert round(size(params) / 1e9, 2) == 9.44
    assert cache.v is None and cache.k.shape == (1, 65538, 16, 512)
    assert cache.state.shape == (4, slots, 64, 128, 128)
    assert cache.conv.shape == (4, slots, 3, 24576)
    assert cache.index["rows"].shape == (1, 65538, 4, 128)
    assert cache.index["tail"].shape == (1, slots, 3, 128)
    assert round(cache.state.size * 4 / 1e9, 2) == 1.07
    assert round(cache.k.size * 2 / 1e9, 2) == 1.07
    assert len(jax.tree.leaves(cache)) == 5 + 2 + 5
    i32 = jnp.int32
    programs = {"decode": make_model_decode_fn(cfg).lower(
        params, cache, sds((slots,), i32), sds((slots,), jnp.bool_))}
    for bucket in (12288,):     # the cell's one bucket
        programs[f"prefill_{bucket}"] = make_model_prefill_fn(cfg).lower(
            params, cache, sds((1, bucket), i32), sds((bucket,), i32),
            sds((), i32), sds((bucket // page,), i32),
            sds((max_len // page,), i32))
    within_mla_budget(programs["decode"].as_text())
    want = {"decode": {"apex_kda_decode_fwd": 4, "apex_kda_chunk_fwd": 0,
                       "apex_dsa_index_fwd": 1, "apex_mla_decode_fwd": 1,
                       "apex_moe_gmm_fwd": 8, "apex_flash_fwd": 0},
            "prefill": {"apex_kda_decode_fwd": 0, "apex_kda_chunk_fwd": 4,
                        "apex_dsa_index_fwd": 0, "apex_mla_decode_fwd": 0,
                        "apex_moe_gmm_fwd": 8, "apex_flash_fwd": 0}}
    for name, lowered in programs.items():
        compiled = lowered.compile()
        text = compiled.as_text()
        kind = name.split("_")[0]
        got = {k: len(re.findall(rf"%{k}(\.\d+)? = ", text))
               for k in want[kind]}
        assert got == want[kind], name
        mem = compiled.memory_analysis()
        print(name, "args", mem.argument_size_in_bytes / 1e9, "temp",
              mem.temp_size_in_bytes / 1e9, "alias",
              mem.alias_size_in_bytes / 1e9, "cache", size(cache) / 1e9,
              (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 2 ** 30)
        assert mem.alias_size_in_bytes >= size(cache), name
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            < 0.96 * 16 * 2 ** 30, name
        if kind == "decode":
            for leaf in (cache.conv, cache.index["tail"]):
                assert moved_rows(text, leaf.shape) == []


def test_flat_adam(v5e):
    from apex_tpu.optimizers import FusedAdam

    opt = FusedAdam(lr=1e-4, weight_decay=0.01, use_flat_kernel=True)

    def make():
        params = {"w": jnp.zeros((1024, 1024)), "b": jnp.zeros((1024,))}
        return params, opt.init(params)

    sharding = SingleDeviceSharding(v5e)
    p, s = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        jax.eval_shape(make))
    text = jax.jit(lambda g, p, s: opt.step(g, p, s)).lower(
        p, p, s).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 1


@pytest.mark.parametrize("preset, batch, seq, fmha", [
    ("bert_tiny", 8, 64, 0), ("bert_large", 256, 128, 24)])
def test_ddp_bert_step_lowers_for_four_chips(v5e_chips, preset, batch, seq,
                                             fmha):
    """The multi-chip route: the data-parallel BERT amp-O2 step of
    ``__graft_entry__`` phase 1 and the ``bert_large.pretrain_s128_dp4``
    cell (the second case: its own sizes, b64 s128 a chip) lowers for four
    chips through ``shard_map``, kernels and all: at s128 each layer's
    attention is ONE ``apex_fmha_fwd`` and ONE ``apex_fmha_bwd`` call and no
    score-shaped array is in the program. The same step as a plain
    ``jit`` over batch-sharded inputs does not — XLA does not partition a
    Mosaic kernel — which the CPU mesh (interpret-mode kernels) cannot
    show. The day the second half fails, the reason ``shard_map`` is the
    only route is gone."""
    import re

    from apex_tpu import amp, models
    from apex_tpu.models import apply_bert, init_bert, mlm_loss
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import DistributedDataParallel
    from apex_tpu.transformer import parallel_state as ps

    ps.destroy_model_parallel()
    mesh = ps.initialize_model_parallel(devices=v5e_chips)
    cfg = getattr(models, preset)()
    h = amp.initialize(opt_level="O2", loss_scale="dynamic", verbosity=0)
    opt = FusedAdam(lr=1e-4)

    def make_state():
        params = init_bert(jax.random.PRNGKey(0), cfg)
        return params, opt.init(params), h.init_state()

    def step(master, opt_state, scaler, ids, mask, ddp=None):
        p = h.cast_model(ddp.local_replica(master) if ddp else master)
        loss, grads, found_inf, scaler = h.value_and_grad(
            lambda p: mlm_loss(apply_bert(p, cfg, ids, mask)["mlm_logits"],
                               ids, mask),
            reduce_grads=ddp.allreduce_grads if ddp else None)(p, scaler)
        master, opt_state = opt.step(grads, master, opt_state,
                                     found_inf=found_inf)
        return master, opt_state, scaler, loss

    def placed(tree, spec):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, spec)), tree)

    rep, data = P(), P(ps.DATA_AXIS)
    args = (*placed(jax.eval_shape(make_state), rep),
            *placed((jax.ShapeDtypeStruct((batch, seq), jnp.int32),) * 2,
                    data))
    try:
        mapped = ps.shard_map(
            functools.partial(step, ddp=DistributedDataParallel()),
            mesh=mesh, in_specs=(rep,) * 3 + (data,) * 2,
            out_specs=(rep,) * 4)
        text = jax.jit(mapped).lower(*args).compile().as_text()
        assert text.count('custom_call_target="tpu_custom_call"') >= 4
        assert " all-reduce(" in text
        calls = lambda name: len(re.findall(       # noqa: E731
            rf"%{name}[.\d]* = [^\n]* custom-call\(", text))
        assert (calls("apex_fmha_fwd"), calls("apex_fmha_bwd")) \
            == (fmha, fmha)
        if fmha:
            assert f"[{batch // 4},{cfg.num_heads},{seq},{seq}]" not in text
        with pytest.raises(NotImplementedError, match="Mosaic kernels"):
            jax.jit(step).lower(*args)
    finally:
        ps.destroy_model_parallel()
