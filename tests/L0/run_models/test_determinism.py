"""Determinism + profiling-hook tests (SURVEY §5 rows 1–2: same seed ⇒
bitwise-equal training; named scopes visible to the tracer)."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import amp
from apex_tpu.models import (
    apply_bert, bert_tiny, gpt_loss_unsharded, gpt_tiny, init_bert,
    init_gpt, mlm_loss,
)
from apex_tpu.optimizers import FusedAdam


def _bert_train_step(seed):
    """One full amp-O2 + FusedAdam + dropout train step, from scratch."""
    cfg = bert_tiny()
    h = amp.initialize(opt_level="O2", loss_scale="dynamic", verbosity=0)
    params = init_bert(jax.random.PRNGKey(0), cfg)
    opt = FusedAdam(lr=1e-3)
    opt_state = opt.init(params)
    scaler_state = h.init_state()
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                             cfg.vocab_size)
    mask = jnp.ones_like(ids)

    @jax.jit
    def step(master, opt_state, scaler_state, rng):
        p = h.cast_model(master)

        def loss_fn(p):
            out = apply_bert(p, cfg, ids, mask, dropout_rng=rng)
            return mlm_loss(out["mlm_logits"], ids, mask)

        loss, grads, found_inf, scaler_state = h.value_and_grad(loss_fn)(
            p, scaler_state)
        master, opt_state = opt.step(grads, master, opt_state,
                                     found_inf=found_inf)
        return master, loss

    master, loss = step(params, opt_state, scaler_state,
                        jax.random.PRNGKey(seed))
    return np.asarray(loss), jax.tree.map(np.asarray, master)


def test_same_seed_bitwise_identical_train_step():
    loss_a, params_a = _bert_train_step(seed=7)
    loss_b, params_b = _bert_train_step(seed=7)
    assert loss_a.tobytes() == loss_b.tobytes()
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(a, b, strict=True),
        params_a, params_b)


def test_different_seed_differs():
    loss_a, _ = _bert_train_step(seed=7)
    loss_c, _ = _bert_train_step(seed=8)
    assert loss_a.tobytes() != loss_c.tobytes()


def test_gpt_dropout_bitwise_deterministic():
    cfg = gpt_tiny()
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                             cfg.vocab_size)
    f = jax.jit(lambda rng: gpt_loss_unsharded(
        params, cfg, ids, ids, dropout_rng=rng))
    a = np.asarray(f(jax.random.PRNGKey(3)))
    b = np.asarray(f(jax.random.PRNGKey(3)))
    assert a.tobytes() == b.tobytes()


def _hlo_with_metadata(lowered):
    """Text form of a lowered computation that still carries scope names.
    Newer jax exposes them on the Lowered (``debug_info=True``); older
    releases strip locs from ``as_text()`` and only the compiled HLO's
    op metadata keeps them."""
    try:
        return lowered.as_text(debug_info=True)
    except TypeError:
        return lowered.compile().as_text()


def _regions_in(lowered):
    """The device regions (``utils.profiler.REGIONS``) on the operation
    paths of a lowered computation, by the trace reader's own rule."""
    import re

    from benchmark.regions import region_of

    # an operation's path has components and starts with none (a scan's
    # body is a function of its own, its paths relative); a file's starts
    # with "/", a stack frame's function name has no component
    paths = re.findall(r'loc\("([^/"][^"]*/[^"]*)"',
                       _hlo_with_metadata(lowered))
    return {region_of(path) for path in paths} - {None}


def _lower_bert_step(ddp: bool):
    """The train step as ``benchmark/runners/bert_pretrain.py`` composes
    it from the library: cast, value_and_grad over model and loss, the
    optimizer; with ``ddp`` inside a one-device ``shard_map``."""
    from jax.sharding import PartitionSpec as P

    cfg = bert_tiny()
    h = amp.initialize(opt_level="O2", loss_scale="dynamic", verbosity=0)
    opt = FusedAdam(lr=1e-3)
    master = init_bert(jax.random.PRNGKey(0), cfg)
    ids = jnp.zeros((2, 16), jnp.int32)
    wrap = None
    if ddp:
        from apex_tpu.parallel import DistributedDataParallel
        from apex_tpu.transformer import parallel_state as ps
        ps.destroy_model_parallel()
        mesh = ps.initialize_model_parallel(devices=jax.devices()[:1])
        wrap = DistributedDataParallel()

    def step(master, opt_state, scaler, ids, mask):
        p = h.cast_model(wrap.local_replica(master) if wrap else master)
        loss, grads, found_inf, scaler = h.value_and_grad(
            lambda p: mlm_loss(apply_bert(p, cfg, ids, mask)["mlm_logits"],
                               ids, mask),
            reduce_grads=wrap.allreduce_grads if wrap else None)(p, scaler)
        master, opt_state = opt.step(grads, master, opt_state,
                                     found_inf=found_inf)
        return master, opt_state, scaler, loss

    if ddp:
        rep, data = P(), P(ps.DATA_AXIS)
        step = ps.shard_map(step, mesh=mesh,
                            in_specs=(rep, rep, rep, data, data),
                            out_specs=(rep, rep, rep, rep))
    try:
        return jax.jit(step).lower(master, opt.init(master), h.init_state(),
                                   ids, jnp.ones_like(ids))
    finally:
        if ddp:
            ps.destroy_model_parallel()


def _lower_gpt(which: str):
    """One of the GPT serving programs at ``gpt_tiny``."""
    from apex_tpu.serving import cache as C
    from apex_tpu.serving import decode as D

    cfg = gpt_tiny()
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    slots, max_len, page = 2, 32, 4
    i32 = jnp.int32
    tokens, active = jnp.zeros((slots,), i32), jnp.ones((slots,), bool)
    ids, mask = jnp.zeros((1, 8), i32), jnp.ones((8,), i32)
    dtype = jnp.int8 if which.endswith("_q8") else jnp.float32
    cache = C.init_paged_cache(cfg, slots, max_len, slots * 8 + 2, page,
                               dtype)
    return {
        "paged_prefill": lambda: D.make_paged_prefill_fn(cfg).lower(
            params, cache, ids, mask, i32(0), jnp.zeros((2,), i32),
            jnp.zeros((8,), i32)),
        "paged_chunk_prefill": lambda: D.make_paged_chunk_prefill_fn(
            cfg).lower(params, cache, ids, mask, i32(0), i32(0),
                       jnp.zeros((2,), i32), jnp.zeros((8,), i32),
                       jnp.zeros((8,), i32)),
        "paged_decode": lambda: D.make_paged_decode_fn(cfg).lower(
            params, cache, tokens, active),
        "paged_decode_q8": lambda: D.make_paged_decode_fn(cfg).lower(
            params, cache, tokens, active),
        "paged_verify": lambda: D.make_paged_verify_fn(cfg).lower(
            params, cache, jnp.zeros((slots, 3), i32)),
        "paged_verify_q8": lambda: D.make_paged_verify_fn(cfg).lower(
            params, cache, jnp.zeros((slots, 3), i32)),
        "paged_tree_verify": lambda: D.make_paged_tree_verify_fn(cfg).lower(
            params, cache, jnp.zeros((slots, 3), i32),
            jnp.zeros((slots, 3), i32), jnp.ones((slots, 3, 3), bool)),
    }[which]()


def _lower_model(family: str, which: str):
    """The prefill or decode program of a model that brings its cores, at
    its tiny size."""
    from apex_tpu.models import (bailing_hybrid, deepseek, exaone_moe, hybrid,
                                 nemotron_h)
    from apex_tpu.serving import cache as C
    from apex_tpu.serving import decode as D

    cfg, init, init_pools = {
        "hybrid": (hybrid.hybrid_tiny(), hybrid.init_hybrid,
                   C.init_hybrid_cache),
        "nemotron_h": (nemotron_h.nemotron_h_tiny(), nemotron_h.init,
                       C.init_hybrid_cache),
        "deepseek": (deepseek.deepseek_tiny(), deepseek.init,
                     C.init_latent_cache),
        "exaone": (exaone_moe.exaone_moe_tiny(), exaone_moe.init,
                   C.init_window_cache),
        "ling": (bailing_hybrid.bailing_hybrid_tiny(), bailing_hybrid.init,
                 C.init_hybrid_cache)}[family]
    params = init(jax.random.PRNGKey(0), cfg)
    slots, max_len, page = 2, 32, 4
    cache = init_pools(cfg, slots, max_len, slots * 8 + 2, page, jnp.float32)
    i32 = jnp.int32
    if which == "decode":
        return D.make_model_decode_fn(cfg).lower(
            params, cache, jnp.zeros((slots,), i32), jnp.ones((slots,), bool))
    return D.make_model_prefill_fn(cfg).lower(
        params, cache, jnp.zeros((1, 8), i32), jnp.ones((8,), i32), i32(0),
        jnp.zeros((2,), i32), jnp.zeros((8,), i32))


_TRAIN = {"embed", "attention", "mlp", "head", "loss", "amp", "optimizer"}
_GPT = {"embed", "attention", "mlp", "head"}
_SPARSE = _GPT | {"router", "experts", "cache_write"}
_SCOPED = [
    ("bert_train_step", lambda: _lower_bert_step(False), _TRAIN),
    ("bert_train_step_ddp", lambda: _lower_bert_step(True),
     _TRAIN | {"grad_sync"}),
    ("gpt_paged_prefill", lambda: _lower_gpt("paged_prefill"),
     _GPT | {"cache_write"}),
    ("gpt_paged_chunk_prefill", lambda: _lower_gpt("paged_chunk_prefill"),
     _GPT),
    ("gpt_paged_decode", lambda: _lower_gpt("paged_decode"),
     _GPT | {"cache_write"}),
    ("gpt_paged_decode_q8", lambda: _lower_gpt("paged_decode_q8"), _GPT),
    ("gpt_paged_verify", lambda: _lower_gpt("paged_verify"), _GPT),
    ("gpt_paged_verify_q8", lambda: _lower_gpt("paged_verify_q8"), _GPT),
    ("gpt_paged_tree_verify", lambda: _lower_gpt("paged_tree_verify"), _GPT),
    ("hybrid_prefill", lambda: _lower_model("hybrid", "prefill"),
     _GPT | {"mixer", "cache_write"}),
    ("hybrid_decode", lambda: _lower_model("hybrid", "decode"),
     _GPT | {"mixer", "cache_write"}),
    ("nemotron_h_prefill", lambda: _lower_model("nemotron_h", "prefill"),
     _SPARSE | {"mixer"}),
    ("nemotron_h_decode", lambda: _lower_model("nemotron_h", "decode"),
     _SPARSE | {"mixer"}),
    ("deepseek_prefill", lambda: _lower_model("deepseek", "prefill"),
     _SPARSE),
    ("deepseek_decode", lambda: _lower_model("deepseek", "decode"), _SPARSE),
    # K-EXAONE's routing runs inside its ``experts`` region
    ("exaone_prefill", lambda: _lower_model("exaone", "prefill"),
     _SPARSE - {"router"}),
    ("exaone_decode", lambda: _lower_model("exaone", "decode"),
     _SPARSE - {"router"}),
    ("ling_prefill", lambda: _lower_model("ling", "prefill"),
     _SPARSE | {"mixer"}),
    ("ling_decode", lambda: _lower_model("ling", "decode"),
     _SPARSE | {"mixer"}),
]


@pytest.mark.parametrize("lower,regions", [c[1:] for c in _SCOPED],
                         ids=[c[0] for c in _SCOPED])
def test_named_scopes_reach_hlo_metadata(lower, regions):
    """The profiler hooks are real: the device regions survive into the
    lowered HLO's metadata (what the trace attributes operations to), in
    the train step as the benchmark's runner composes it and in every
    serving program, and each program holds exactly the regions its model
    has."""
    lowered = lower()
    assert _regions_in(lowered) == regions
    if "optimizer" in regions:      # the scopes PR 24's readers lean on
        txt = _hlo_with_metadata(lowered)
        assert "layer0)/attention" in txt or "layer0/attention" in txt
        assert "layer0)/mlp" in txt or "layer0/mlp" in txt
        assert "optimizer/FusedAdam.step" in txt


def test_inference_forward_keeps_layer_scopes():
    cfg = bert_tiny()
    params = init_bert(jax.random.PRNGKey(0), cfg)
    ids = jnp.zeros((1, 16), jnp.int32)
    txt = _hlo_with_metadata(jax.jit(
        lambda p: apply_bert(p, cfg, ids, jnp.ones_like(ids))["hidden"]
    ).lower(params))
    assert "layer0/attention" in txt
    assert "layer0/mlp" in txt


def test_region_refuses_a_name_outside_the_vocabulary():
    from apex_tpu.utils.profiler import REGIONS, region

    with pytest.raises(ValueError, match="not a device region"):
        region("nonsense")
    assert len(set(REGIONS)) == len(REGIONS) == 12
    with region("attention"):       # and is a plain named scope otherwise
        pass


def test_profiler_trace_writes_files(tmp_path):
    from apex_tpu.utils.profiler import annotate, trace

    with trace(str(tmp_path)):
        with annotate("traced_region"):
            jnp.dot(jnp.ones((64, 64)), jnp.ones((64, 64))
                    ).block_until_ready()
    found = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert found, f"no trace written under {tmp_path}"
