"""Trace-tier entry registry and driver.

A :class:`TraceEntry` names a *traceable entrypoint* — a representative
invocation of a kernel, an optimizer, an amp-wrapped train step, or a
parallel schedule — and the jaxpr-level verifiers to run over it. The
driver traces each entry under ``jax.make_jaxpr`` (abstract only, no
compile, CPU-safe) and dispatches to the APX5xx checkers; an entry that
fails to trace at all is an APX100 finding, never a silent skip (same
contract as the APX102 VMEM registry).

Builder conventions:

- ``build()`` returns ``(fn, args)`` where args are
  ``jax.ShapeDtypeStruct`` trees — nothing is materialized;
- entries with the ``amp`` check make ``fn``'s FIRST flat argument the
  loss-scale scalar and return ``(protected_state, aux)`` where
  ``protected_state`` is the tree of optimizer-state writes (new
  params + optimizer state) — :func:`precision.check_amp` seeds and
  reads taint by those positions;
- entries that need the global mesh set ``mesh`` to a thunk calling
  ``parallel_state.initialize_model_parallel``; the driver snapshots
  and restores the parallel state around every entry.

The registry needs the 8-virtual-device CPU world the test rig uses
(pipeline/TP/context entries shard over it); ``ensure_cpu_devices``
arranges that BEFORE first backend use, and degrades to APX100 findings
for mesh entries when the backend was already initialized too small.
"""

import functools
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Tuple

from apex_tpu.lint import Finding

_DEFAULT_DEVICES = 8


@dataclass
class TraceEntry:
    name: str
    module: str  # dotted module whose contract this entry exercises
    build: Callable[[], Tuple[Callable, tuple]]
    checks: Tuple[str, ...] = ("precision", "memory")
    mesh: Optional[Callable[[], None]] = None
    min_devices: int = 1
    min_alias_pairs: int = 0
    blowup_factor: float = 8.0
    blowup_floor: int = 1 << 20


def ensure_cpu_devices(n: int = _DEFAULT_DEVICES) -> int:
    """Best-effort: give this process an ``n``-device CPU world.

    Only effective before the jax backend initializes (the lint CLI
    calls it first thing; under pytest the conftest has already done
    the equivalent). Afterwards it is a no-op and the caller sees the
    actual device count.
    """
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", n)
    except RuntimeError:
        pass  # backend already initialized: the caller sees its world
    return jax.device_count()


def _snapshot_parallel_state():
    from apex_tpu.transformer import parallel_state as ps

    return (ps._MESH,
            ps._VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE,
            ps._VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK,
            ps._PIPELINE_MODEL_PARALLEL_SPLIT_RANK)


def _restore_parallel_state(snap) -> None:
    from apex_tpu.transformer import parallel_state as ps

    (ps._MESH,
     ps._VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE,
     ps._VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK,
     ps._PIPELINE_MODEL_PARALLEL_SPLIT_RANK) = snap


def _module_path(dotted: str) -> str:
    import importlib

    try:
        return importlib.import_module(dotted).__file__ or dotted
    except Exception:  # noqa: BLE001
        return dotted


def run_entries(entries: List[TraceEntry], *, run_checks: bool = True,
                cost_out: Optional[list] = None) -> List[Finding]:
    """Trace every entry and run its checks; APX100 on trace failure.

    Each entry is traced exactly once. With ``run_checks`` the APX5xx
    verifiers run over the jaxpr; with ``cost_out`` a
    :class:`~apex_tpu.lint.traced.cost.CostReport` per entry is
    appended to that list (APX100 if cost analysis itself fails) — the
    ``--trace --cost`` CLI combination shares the single trace.
    """
    ensure_cpu_devices()
    import jax

    from apex_tpu.lint.traced import aliases, memory, precision, schedule

    findings: List[Finding] = []
    for e in entries:
        path = _module_path(e.module)
        snap = _snapshot_parallel_state()
        try:
            try:
                have = jax.device_count()
                if have < e.min_devices:
                    raise RuntimeError(
                        f"needs {e.min_devices} devices, have {have} "
                        f"(backend initialized before ensure_cpu_devices)")
                if e.mesh is not None:
                    e.mesh()
                fn, args = e.build()
                closed, out_shape = jax.make_jaxpr(
                    fn, return_shape=True)(*args)
            finally:
                _restore_parallel_state(snap)
        except Exception as exc:  # noqa: BLE001 - surfaced as a finding
            findings.append(Finding(
                "APX100", path, 1,
                f"trace entry '{e.name}' failed to trace: "
                f"{type(exc).__name__}: {exc}"))
            continue

        if cost_out is not None:
            from apex_tpu.lint.traced import cost

            try:
                cost_out.append(cost.compute(closed, path, e.name))
            except Exception as exc:  # noqa: BLE001 - surfaced
                findings.append(Finding(
                    "APX100", path, 1,
                    f"trace entry '{e.name}' cost analysis failed: "
                    f"{type(exc).__name__}: {exc}"))

        if not run_checks:
            continue
        if "precision" in e.checks:
            findings.extend(precision.check_reductions(closed, path, e.name))
        if "amp" in e.checks:
            prot = out_shape[0] if isinstance(out_shape, tuple) else out_shape
            n_prot = len(jax.tree_util.tree_leaves(prot))
            findings.extend(precision.check_amp(closed, path, e.name,
                                                n_prot))
        if "memory" in e.checks:
            findings.extend(memory.check(closed, path, e.name,
                                         factor=e.blowup_factor,
                                         floor=e.blowup_floor))
        if "schedule" in e.checks:
            findings.extend(schedule.check(closed, path, e.name))
        if "aliases" in e.checks:
            findings.extend(aliases.check(
                closed, path, e.name, min_alias_pairs=e.min_alias_pairs))
    return findings


# ---------------------------------------------------------------------------
# registered repo entrypoints
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def _flash_entry(d, dtype, seq):
    def build():
        import jax
        import jax.numpy as jnp

        from apex_tpu.transformer.functional.flash_attention import (
            flash_attention,
        )

        def loss(q, k, v):
            out = flash_attention(q, k, v, causal=True, use_kernel=True)
            # squared so the cotangent is data-dependent, not a
            # broadcast-of-ones (which would trip APX503 on the harness)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        fn = lambda q, k, v: jax.value_and_grad(loss, (0, 1, 2))(q, k, v)
        shape = (1, 2, seq, d)
        return fn, (_sds(shape, dtype),) * 3

    return build


def _fmha_entry():
    """The whole-sequence pair on a packed projection at s128, four heads of
    64, forward and backward (``models/bert.py``'s call)."""
    def build():
        import jax
        import jax.numpy as jnp

        from apex_tpu.transformer.functional.flash_attention import (
            flash_attention_packed,
        )

        def loss(qkv):
            out = flash_attention_packed(qkv)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        return jax.value_and_grad(loss), (
            _sds((2, 128, 3, 4, 64), "bfloat16"),)

    return build


def _ln_entry(h, rms=False):
    def build():
        import importlib

        import jax
        import jax.numpy as jnp

        fln = importlib.import_module(
            "apex_tpu.normalization.fused_layer_norm")

        if rms:
            def loss(x, w):
                y = fln.fused_rms_norm_affine(x, w, (h,))
                return jnp.sum(y.astype(jnp.float32) ** 2)
            args = (_sds((2048, h), "float32"), _sds((h,), "float32"))
            return (lambda *a: jax.value_and_grad(loss, (0, 1))(*a)), args

        def loss(x, w, b):
            y = fln.fused_layer_norm_affine(x, w, b, (h,))
            return jnp.sum(y.astype(jnp.float32) ** 2)
        args = (_sds((2048, h), "float32"), _sds((h,), "float32"),
                _sds((h,), "float32"))
        return (lambda *a: jax.value_and_grad(loss, (0, 1, 2))(*a)), args

    return build


def _xentropy_entry():
    def build():
        import jax

        from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss

        def loss(logits, labels):
            return softmax_cross_entropy_loss(logits, labels).mean()

        fn = lambda lg, lb: jax.value_and_grad(loss)(lg, lb)
        return fn, (_sds((1024, 512), "float32"), _sds((1024,), "int32"))

    return build


def _flat_entry(which):
    rows = 8192  # aligned to the block multiple: no pad, alias survives

    def build():
        import functools as ft

        from apex_tpu.multi_tensor_apply import kernels as K

        buf = _sds((rows, 128), "float32")
        m16 = _sds((rows, 128), "bfloat16")
        ids = _sds((rows // 8,), "int32")
        if which == "adam":
            fn = ft.partial(K.flat_adam, lr=1e-3, beta1=0.9, beta2=0.99,
                            eps=1e-8, step=1, weight_decay=0.01,
                            interpret=True)
            return fn, (buf, buf, buf, buf)
        if which == "sgd":
            fn = ft.partial(K.flat_sgd, lr=1e-3, momentum=0.9,
                            dampening=0.0, weight_decay=0.0,
                            nesterov=False, wd_after_momentum=False,
                            first_run=True, interpret=True)
            return fn, (buf, buf, m16)
        if which == "lamb":
            fn = ft.partial(K.flat_lamb, lr=1e-3, beta1=0.9, beta2=0.99,
                            eps=1e-8, step=1, weight_decay=0.01,
                            num_tensors=4, interpret=True)
            return fn, (buf, buf, m16, buf, ids)
        if which == "adagrad":
            fn = ft.partial(K.flat_adagrad, lr=1e-3, eps=1e-8,
                            weight_decay=0.0, interpret=True)
            return fn, (buf, buf, buf)
        fn = ft.partial(K.flat_novograd, lr=1e-3, beta1=0.9,
                        beta2=0.99, eps=1e-8, step=1, weight_decay=0.0,
                        num_tensors=4, interpret=True)
        return fn, (buf, buf, m16, _sds((4,), "float32"), ids)

    return build


def _fused_adam_tree_entry():
    def build():
        import jax

        from apex_tpu.optimizers.fused_adam import FusedAdam

        opt = FusedAdam(lr=1e-3, use_flat_kernel=False)
        params = {"w": _sds((256, 128), "float32"),
                  "b": _sds((128,), "float32")}
        state = jax.eval_shape(opt.init, params)

        def step(grads, params, state):
            return opt.step(grads, params, state)

        return step, (params, params, state)

    return build


def _amp_o2_step_entry(model):
    """O2 amp train step over a tiny model; the APX502 subject.

    fn layout (the check_amp convention): first arg = loss-scale
    scalar, first output = (new master params, new optimizer state).
    """
    def build():
        import jax
        import jax.numpy as jnp

        from apex_tpu import amp
        from apex_tpu.amp.scaler import LossScalerState
        from apex_tpu.optimizers.fused_adam import AdamState, FusedAdam

        h = amp.initialize("O2", verbosity=0, loss_scale="dynamic")
        opt = FusedAdam(lr=1e-3, use_flat_kernel=False)

        if model == "bert":
            from apex_tpu.models.bert import (
                apply_bert, bert_tiny, init_bert, mlm_loss,
            )

            cfg = bert_tiny()
            master = jax.eval_shape(
                lambda k: init_bert(k, cfg), jax.random.PRNGKey(0))
            batch = {"ids": _sds((2, 32), "int32"),
                     "labels": _sds((2, 32), "int32")}

            def loss_fn(p, b):
                out = apply_bert(p, cfg, b["ids"])
                mask = jnp.ones_like(b["labels"], jnp.float32)
                return mlm_loss(out["mlm_logits"], b["labels"], mask)
        else:
            from apex_tpu.models.gpt import (
                gpt_loss_unsharded, gpt_tiny, init_gpt,
            )

            cfg = gpt_tiny()
            master = jax.eval_shape(
                lambda k: init_gpt(k, cfg), jax.random.PRNGKey(0))
            batch = {"ids": _sds((2, 32), "int32"),
                     "labels": _sds((2, 32), "int32")}

            def loss_fn(p, b):
                return gpt_loss_unsharded(p, cfg, b["ids"], b["labels"])

        mstate = jax.eval_shape(opt.init, master)

        def step(loss_scale, master, m, v, stepc, batch):
            state = LossScalerState(
                loss_scale=loss_scale,
                unskipped=jnp.zeros((), jnp.int32),
                overflows=jnp.zeros((), jnp.int32))
            params = h.cast_model(master)
            loss, grads, found_inf, new_state = h.value_and_grad(
                loss_fn)(params, state, batch)
            new_master, new_mstate = opt.step(
                grads, master, AdamState(stepc, m, v),
                found_inf=found_inf)
            return (new_master, new_mstate), (loss, new_state.loss_scale)

        args = (_sds((), "float32"), master, mstate.m, mstate.v,
                _sds((), mstate.step.dtype), batch)
        return step, args

    return build


# --- tiny pipeline harness (mirrors tests/L0/run_transformer) ---------------

_PP_VOCAB, _PP_SEQ, _PP_HIDDEN, _PP_FF = 64, 8, 16, 32


def _pp_model():
    import jax
    import jax.numpy as jnp

    from apex_tpu.transformer.pipeline_parallel import PipelineModel

    def embed_fn(p, mb):
        x = p["word"][mb["ids"]]
        return x + p["pos"][None, : x.shape[1]]

    def stage_fn(p, x):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        h = (x - mu) * jax.lax.rsqrt(var + 1e-5) * p["ln_w"] + p["ln_b"]
        h = jax.nn.gelu(h @ p["fc1"] + p["b1"]) @ p["fc2"] + p["b2"]
        return x + h

    def loss_fn(p, x, mb):
        logits = x @ p["proj"] + p["bias"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, mb["labels"][..., None], -1)[..., 0]
        return -ll.mean()

    return PipelineModel(embed_fn, stage_fn, loss_fn)


def _pp_args(n_stages, batch, stage_lead=()):
    v, s, hd, ff = _PP_VOCAB, _PP_SEQ, _PP_HIDDEN, _PP_FF
    params = {
        "embed": {"word": _sds((v, hd), "float32"),
                  "pos": _sds((s, hd), "float32")},
        "stages": {
            "ln_w": _sds(stage_lead + (n_stages, hd), "float32"),
            "ln_b": _sds(stage_lead + (n_stages, hd), "float32"),
            "fc1": _sds(stage_lead + (n_stages, hd, ff), "float32"),
            "b1": _sds(stage_lead + (n_stages, ff), "float32"),
            "fc2": _sds(stage_lead + (n_stages, ff, hd), "float32"),
            "b2": _sds(stage_lead + (n_stages, hd), "float32"),
        },
        "head": {"proj": _sds((hd, v), "float32"),
                 "bias": _sds((v,), "float32")},
    }
    mb = {"ids": _sds((batch, s), "int32"),
          "labels": _sds((batch, s), "int32")}
    return params, mb


def _pp_1f1b_entry(pp, n_mb):
    def build():
        from jax.sharding import PartitionSpec as P

        from apex_tpu.transformer import parallel_state as ps
        from apex_tpu.transformer.pipeline_parallel import (
            forward_backward_pipelining_without_interleaving,
        )

        model = _pp_model()
        params, mb = _pp_args(pp, 2 * n_mb)
        tree_spec = {"embed": P(), "stages": P(ps.PIPE_AXIS), "head": P()}
        fn = ps.shard_map(
            lambda p, b: forward_backward_pipelining_without_interleaving(
                model, p, b, num_microbatches=n_mb),
            in_specs=(tree_spec, P()),
            out_specs=(P(), tree_spec))
        return fn, (params, mb)

    return build


def _pp_interleaved_entry(pp, vpp, n_mb):
    def build():
        from jax.sharding import PartitionSpec as P

        from apex_tpu.transformer import parallel_state as ps
        from apex_tpu.transformer.pipeline_parallel import (
            forward_backward_pipelining_with_interleaving,
        )

        model = _pp_model()
        params, mb = _pp_args(pp, 2 * n_mb, stage_lead=(vpp,))
        tree_spec = {"embed": P(), "stages": P(None, ps.PIPE_AXIS),
                     "head": P()}
        fn = ps.shard_map(
            lambda p, b: forward_backward_pipelining_with_interleaving(
                model, p, b, num_microbatches=n_mb),
            in_specs=(tree_spec, P()),
            out_specs=(P(), tree_spec))
        return fn, (params, mb)

    return build


def _pp_sequential_entry():
    def build():
        from apex_tpu.transformer.pipeline_parallel import (
            forward_backward_no_pipelining,
        )

        model = _pp_model()
        params, mb = _pp_args(3, 4)
        fn = lambda p, b: forward_backward_no_pipelining(
            model, p, b, num_microbatches=2)
        return fn, (params, mb)

    return build


def _tp_block_entry(tp):
    def build():
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from apex_tpu.transformer import parallel_state as ps
        from apex_tpu.transformer import tensor_parallel as tpmod

        col = tpmod.ColumnParallelLinear(32, 64, gather_output=False)
        row = tpmod.RowParallelLinear(64, 32, input_is_parallel=True)

        def loss(cp, rp, x):
            y = row.apply(rp, jax.nn.gelu(col.apply(cp, x)))
            return jnp.sum((y.astype(jnp.float32)) ** 2)

        fn = ps.shard_map(
            lambda cp, rp, x: jax.value_and_grad(loss, (0, 1))(cp, rp, x),
            in_specs=(col.partition_specs(), row.partition_specs(), P()),
            out_specs=(P(), (col.partition_specs(),
                             row.partition_specs())))
        cp = jax.eval_shape(lambda k: col.init(k), jax.random.PRNGKey(0))
        rp = jax.eval_shape(lambda k: row.init(k), jax.random.PRNGKey(1))
        return fn, (cp, rp, _sds((4, 32), "float32"))

    return build


def bottleneck_parts():
    """The spatial-parallel bottleneck halo exchange: conv stack whose
    width dim shards over ``context``, ring-ppermute halos at the shard
    edges. Returns ``(fn, args, in_specs)`` so the APX9xx scaling tier
    can re-stage it across swept ``cp`` sizes (the width of 16 divides
    every swept context size); the caller's mesh sets the ``context``
    axis size."""
    from jax.sharding import PartitionSpec as P

    from apex_tpu.contrib.bottleneck import spatial_parallel_bottleneck
    from apex_tpu.transformer import parallel_state as ps

    params = {"w1": _sds((1, 1, 8, 4), "float32"),
              "w2": _sds((3, 3, 4, 4), "float32"),
              "w3": _sds((1, 1, 4, 8), "float32")}
    # one spec per flattened operand (not a pytree-prefix P()) so the
    # APX703/903 taint walk sees the same operand count shard_map does
    in_specs = ({k: P() for k in sorted(params)},
                P(None, ps.CONTEXT_AXIS))
    fn = ps.shard_map(
        spatial_parallel_bottleneck,
        in_specs=in_specs,
        out_specs=P(None, ps.CONTEXT_AXIS))
    return fn, (params, _sds((2, 16, 5, 8), "float32")), in_specs


def _bottleneck_entry():
    def build():
        fn, args, _ = bottleneck_parts()
        return fn, args

    return build


def _serving_cfg():
    import dataclasses

    from apex_tpu.models.gpt import gpt_tiny

    return dataclasses.replace(gpt_tiny(), use_rope=True)


def _paged_serving_args(cfg, num_slots=2, max_len=32, num_pages=6,
                        page_size=16):
    import functools as ft

    import jax

    from apex_tpu.models.gpt import init_gpt
    from apex_tpu.serving.cache import init_paged_cache

    params = jax.eval_shape(
        lambda k: init_gpt(k, cfg), jax.random.PRNGKey(0))
    cache = jax.eval_shape(ft.partial(
        init_paged_cache, cfg, num_slots, max_len, num_pages, page_size))
    return params, cache


def _paged_prefill_step_entry():
    """Paged prefill: one 16-token bucket = one page tile scattered to
    ``write_pages`` plus the slot's block-table row — all four cache
    leaves (pool k/v, lengths, block tables) written in place."""
    def build():
        from apex_tpu.serving.decode import make_paged_prefill_fn

        cfg = _serving_cfg()
        params, cache = _paged_serving_args(cfg)
        fn = make_paged_prefill_fn(cfg)
        return fn, (params, cache, _sds((1, 16), "int32"),
                    _sds((16,), "int32"), _sds((), "int32"),
                    _sds((1,), "int32"), _sds((2,), "int32"))

    return build


def _paged_chunk_prefill_step_entry():
    """Paged chunked prefill: a 16-token = one-page chunk scattered to
    ``write_pages`` while attention gathers through the slot's real
    ``gather_row`` (earlier chunks + shared prefix visible) and
    ``store_row`` lands in the block table — the same 4-leaf donated
    cache as monolithic paged prefill."""
    def build():
        from apex_tpu.serving.decode import make_paged_chunk_prefill_fn

        cfg = _serving_cfg()
        params, cache = _paged_serving_args(cfg)
        fn = make_paged_chunk_prefill_fn(cfg)
        return fn, (params, cache, _sds((1, 16), "int32"),
                    _sds((16,), "int32"), _sds((), "int32"),
                    _sds((), "int32"), _sds((1,), "int32"),
                    _sds((2,), "int32"), _sds((2,), "int32"))

    return build


def _paged_chunk_prefill_step_medium_entry():
    """r14 cost anchor: one 256-token chunk of a long prompt at the
    ragged medium pool shape (32 slots, s_max 512, page 64, bf16
    params). Its budgets.json row against the monolithic-prefill read
    pins the chunking price: ~chunk/S of the parameter+activation work
    plus the re-read of the cache written so far — the bytes the
    scheduler trades for bounded p99 inter-token latency."""
    def build():
        import functools as ft

        import jax
        import jax.numpy as jnp

        from apex_tpu.models.gpt import GPTConfig, init_gpt
        from apex_tpu.serving.cache import RESERVED_PAGES, init_paged_cache
        from apex_tpu.serving.decode import make_paged_chunk_prefill_fn

        cfg = GPTConfig(use_rope=True)
        slots, s_max, page = 32, 512, 64
        lengths = [32 + round(i * (s_max - 32) / (slots - 1))
                   for i in range(slots)]
        num_pages = RESERVED_PAGES + sum(-(-l // page) for l in lengths)
        params = jax.eval_shape(
            lambda k: init_gpt(k, cfg, jnp.bfloat16), jax.random.PRNGKey(0))
        cache = jax.eval_shape(ft.partial(
            init_paged_cache, cfg, slots, s_max, num_pages, page))
        fn = make_paged_chunk_prefill_fn(cfg)
        return fn, (params, cache, _sds((1, 256), "int32"),
                    _sds((256,), "int32"), _sds((), "int32"),
                    _sds((), "int32"), _sds((4,), "int32"),
                    _sds((8,), "int32"), _sds((8,), "int32"))

    return build


def _page_handoff_medium_entry():
    """r15 cost anchor: the receiver half of a disaggregated page
    handoff — ``serving.transfer.make_insert_pages_fn`` scattering one
    full prompt's tiles (8 pages x 64 tokens = a 512-token prompt)
    into the ragged medium pool (32 slots, s_max 512, page 64, bf16).
    The donated in-place scatter prices the handoff at ~the shipped
    tile bytes (2 x L x H x page x head_dim x 2 per page), to be set
    against a decode step's parameter read — the bytes disaggregation moves once per prompt to
    unblock every co-tenant decode tick."""
    def build():
        import functools as ft

        import jax

        from apex_tpu.models.gpt import GPTConfig
        from apex_tpu.serving.cache import RESERVED_PAGES, init_paged_cache
        from apex_tpu.serving.transfer import make_insert_pages_fn

        cfg = GPTConfig(use_rope=True)
        slots, s_max, page = 32, 512, 64
        lengths = [32 + round(i * (s_max - 32) / (slots - 1))
                   for i in range(slots)]
        num_pages = RESERVED_PAGES + sum(-(-l // page) for l in lengths)
        cache = jax.eval_shape(ft.partial(
            init_paged_cache, cfg, slots, s_max, num_pages, page))
        n = s_max // page  # one max-length prompt's page tile
        tile = _sds((cfg.num_layers, n, page,
                     cfg.num_heads * cfg.head_dim), "bfloat16")
        fn = make_insert_pages_fn()
        return fn, (cache, _sds((n,), "int32"), tile, tile)

    return build


def _page_reshard_medium_entry():
    """r17 cost anchor: the sender half of a DEVICE-TO-DEVICE page
    reshard — ``serving.transfer.make_reshard_extract_fn`` gathering
    one full prompt's tiles (8 pages x 64 tokens = a 512-token prompt)
    out of the ragged medium pool (32 slots, s_max 512, page 64, bf16)
    with the head axis sharded tp=2 over ``model``. The explicit tiled
    ``all_gather`` is the whole point of the entry: APX511's per-rank
    simulator verifies both ranks run the identical collective, and
    budgets.json pins the per-prompt collective volume ((tp-1)/tp of
    the tile bytes per rank on the ICI/DCN wire) that the pool
    router's per-link clock prices at ``ici_ticks_per_page`` /
    ``dcn_ticks_per_page`` — the spec-to-spec alternative to the host
    bounce's full gather + re-placement budgeted by
    ``gpt_page_handoff_medium``."""
    def build():
        import functools as ft

        import jax

        from apex_tpu.models.gpt import GPTConfig
        from apex_tpu.serving.cache import RESERVED_PAGES, init_paged_cache
        from apex_tpu.serving.transfer import make_reshard_extract_fn

        cfg = GPTConfig(use_rope=True)
        slots, s_max, page = 32, 512, 64
        lengths = [32 + round(i * (s_max - 32) / (slots - 1))
                   for i in range(slots)]
        num_pages = RESERVED_PAGES + sum(-(-l // page) for l in lengths)
        cache = jax.eval_shape(ft.partial(
            init_paged_cache, cfg, slots, s_max, num_pages, page))
        n = s_max // page  # one max-length prompt's page tile
        fn = make_reshard_extract_fn()
        return fn, (cache, _sds((n,), "int32"))

    return build


def _page_spill_extract_medium_entry():
    """r16 cost anchor: the sender half of a host-tier spill —
    ``serving.transfer.make_extract_pages_fn`` gathering one full
    prompt's tiles (8 pages x 64 tokens) out of the ragged medium pool
    (32 slots, s_max 512, page 64, bf16) on their way to the
    :class:`~apex_tpu.serving.paging.PrefixRegistry`. The gather
    prices a spill at ~the page tile bytes, the same per-page unit the
    handoff entry pins; set against a decode step's parameter read it
    is the reasoning behind ``promote_ticks_per_page``."""
    def build():
        import functools as ft

        import jax

        from apex_tpu.models.gpt import GPTConfig
        from apex_tpu.serving.cache import RESERVED_PAGES, init_paged_cache
        from apex_tpu.serving.transfer import make_extract_pages_fn

        cfg = GPTConfig(use_rope=True)
        slots, s_max, page = 32, 512, 64
        lengths = [32 + round(i * (s_max - 32) / (slots - 1))
                   for i in range(slots)]
        num_pages = RESERVED_PAGES + sum(-(-l // page) for l in lengths)
        cache = jax.eval_shape(ft.partial(
            init_paged_cache, cfg, slots, s_max, num_pages, page))
        n = s_max // page
        fn = make_extract_pages_fn()
        return fn, (cache, _sds((n,), "int32"))

    return build


def _page_promote_insert_quant_medium_entry():
    """r16 cost anchor: a host-tier promotion into the INT8 pool —
    ``serving.transfer.make_insert_pages_quant_fn`` scattering one
    prompt's quantized tiles plus their per-page-per-head scale planes
    back into HBM. The int8 payload is half the bf16 handoff's bytes
    (the scale planes are noise: L x n x H fp32 values per side), which
    is the capacity-doubling arithmetic of BOTH
    tiers — the registry budgets bytes, so kv8 doubles its page count
    exactly as it does HBM's."""
    def build():
        import functools as ft

        import jax
        import jax.numpy as jnp

        from apex_tpu.models.gpt import GPTConfig
        from apex_tpu.serving.cache import RESERVED_PAGES, init_paged_cache
        from apex_tpu.serving.transfer import make_insert_pages_quant_fn

        cfg = GPTConfig(use_rope=True)
        slots, s_max, page = 32, 512, 64
        lengths = [32 + round(i * (s_max - 32) / (slots - 1))
                   for i in range(slots)]
        num_pages = RESERVED_PAGES + sum(-(-l // page) for l in lengths)
        cache = jax.eval_shape(ft.partial(
            init_paged_cache, cfg, slots, s_max, num_pages, page,
            jnp.int8))
        n = s_max // page
        tile = _sds((cfg.num_layers, n, page,
                     cfg.num_heads * cfg.head_dim), "int8")
        scale = _sds((cfg.num_layers, n, cfg.num_heads), "float32")
        fn = make_insert_pages_quant_fn()
        return fn, (cache, _sds((n,), "int32"), tile, tile, scale,
                    scale)

    return build


def _paged_decode_step_entry(tp=None):
    """Paged decode: scatter the new row through the block table, then
    gather each slot's pages and attend (APX105 pins this file's
    registration for the new gather/scatter entrypoints)."""
    def build():
        from apex_tpu.serving.decode import (
            make_paged_decode_fn, make_tp_paged_decode_fn,
        )

        cfg = _serving_cfg()
        params, cache = _paged_serving_args(cfg)
        if tp is None:
            fn = make_paged_decode_fn(cfg)
        else:
            from apex_tpu.models.gpt import GPTModel

            fn = make_tp_paged_decode_fn(GPTModel(cfg, tp_size=tp))
        return fn, (params, cache, _sds((2,), "int32"), _sds((2,), "bool"))

    return build


def _model_step_entry(family, which):
    """The server's two programs for a model that brings its own cores
    (``serving.decode``, "the seam"), at the family's tiny preset.
    ``hybrid`` (``models.hybrid``): prefill runs the
    chunked Gated DeltaNet kernel and flash attention and overwrites one
    slot's state; decode steps every slot's state through
    ``apex_gdn_decode_fwd``. ``nemotron_h`` (``models.nemotron_h``): prefill
    runs the chunked Mamba-2 scan, flash attention and the grouped expert
    product ``apex_moe_gmm_fwd``; decode steps every slot's state through
    ``apex_ssd_decode_fwd``, attends over two K/V heads and counts what its
    held experts got. Both donate the cache (pool k/v, lengths, block tables,
    recurrent state, convolution tails; and the second family's three
    counters). ``deepseek`` (``models.deepseek``): prefill expands keys and
    values from the latent rows it writes and runs flash attention and the
    grouped expert product; decode attends over ONE pool of latent rows
    through ``apex_mla_decode_fwd`` and donates it (the pool, lengths, block
    tables and three counters: 6 pairs). ``exaone_moe``
    (``models.exaone_moe``): prefill runs flash attention, banded in the
    sliding layers, and writes a slot's cycle of window pages beside its
    pages of the full pool; decode attends over both pools through the paged
    decode kernel, the sliding layers' call bounded below, and donates them
    (the two pools' k/v, lengths, block tables and three counters: 9 pairs).
    ``ling`` (``models.bailing_hybrid``): prefill runs the chunked delta rule
    with a decay per key channel (``apex_kda_chunk_fwd``), flash attention
    over keys and values expanded from the one MLA layer's latent rows, and
    the grouped expert product; decode steps every slot's state through
    ``apex_kda_decode_fwd`` and attends over the latent pool through
    ``apex_mla_decode_fwd``; both donate state, tails and ONE pool (no
    ``v``), lengths, block tables and three counters: 8 pairs.
    ``glm`` (``models.glm_next``): four residual streams under hyper-
    connections; prefill takes the prompt a stretch at a time through
    ``apex_kda_chunk_fwd`` (from the state the stretch before left) and the
    sparse layer's masked attention by query blocks; decode runs
    ``apex_kda_decode_fwd``, ``apex_dsa_index_fwd``, an exact top-k, the gather
    and ``apex_mla_decode_fwd``; both donate state, tails, ONE pool, lengths,
    block tables, the index's keys and tails and five counters: 12 pairs.
    These entries are the kernel families' registration."""
    def build():
        import functools as ft

        import jax

        from apex_tpu.serving.cache import (
            init_hybrid_cache, init_latent_cache, init_window_cache,
        )
        from apex_tpu.serving.decode import (
            make_model_decode_fn, make_model_prefill_fn,
        )

        init_pools = init_hybrid_cache
        if family == "hybrid":
            from apex_tpu.models.hybrid import hybrid_tiny, init_hybrid
            cfg, init = hybrid_tiny(), init_hybrid
        elif family == "nemotron_h":
            from apex_tpu.models.nemotron_h import init, nemotron_h_tiny
            cfg = nemotron_h_tiny()
        elif family == "deepseek":
            from apex_tpu.models.deepseek import deepseek_tiny, init
            cfg, init_pools = deepseek_tiny(), init_latent_cache
        elif family == "ling":
            from apex_tpu.models.bailing_hybrid import (
                bailing_hybrid_tiny, init,
            )
            cfg = bailing_hybrid_tiny()
        elif family == "glm":
            from apex_tpu.models.glm_next import glm_next_tiny, init
            cfg = glm_next_tiny()
        else:
            from apex_tpu.models.exaone_moe import exaone_moe_tiny, init
            cfg, init_pools = exaone_moe_tiny(), init_window_cache
        params = jax.eval_shape(
            lambda k: init(k, cfg), jax.random.PRNGKey(0))
        cache = jax.eval_shape(ft.partial(init_pools, cfg, 2, 32, 6, 16))
        if which == "prefill":
            return make_model_prefill_fn(cfg), (
                params, cache, _sds((1, 16), "int32"), _sds((16,), "int32"),
                _sds((), "int32"), _sds((1,), "int32"), _sds((2,), "int32"))
        return make_model_decode_fn(cfg), (
            params, cache, _sds((2,), "int32"), _sds((2,), "bool"))

    return build


def _paged_decode_step_medium_ragged_entry():
    """The decode roofline shape: gpt_medium-class decode, bf16 params,
    32 slots, the pool sized to a RAGGED length ladder (uniform 32..512,
    page size 64) — Σ ceil(len/64) pages plus the two reserved ones — so
    the cost tier's K/V read term is proportional to tokens actually
    held, not slots x S_max. Cost-tier only — APX5xx already runs on the
    tiny-shape decode entries."""
    def build():
        import functools as ft

        import jax
        import jax.numpy as jnp

        from apex_tpu.models.gpt import GPTConfig, init_gpt
        from apex_tpu.serving.cache import RESERVED_PAGES, init_paged_cache
        from apex_tpu.serving.decode import make_paged_decode_fn

        cfg = GPTConfig(use_rope=True)
        slots, s_max, page = 32, 512, 64
        lengths = [32 + round(i * (s_max - 32) / (slots - 1))
                   for i in range(slots)]
        num_pages = RESERVED_PAGES + sum(-(-l // page) for l in lengths)
        params = jax.eval_shape(
            lambda k: init_gpt(k, cfg, jnp.bfloat16), jax.random.PRNGKey(0))
        cache = jax.eval_shape(ft.partial(
            init_paged_cache, cfg, slots, s_max, num_pages, page))
        fn = make_paged_decode_fn(cfg)
        return fn, (params, cache, _sds((slots,), "int32"),
                    _sds((slots,), "bool"))

    return build


def _spec_verify_step_entry(tp=None):
    """Speculative verify: k+1 = 4 candidate positions per slot against
    the paged pool — k1 unrolled row scatters through the block table,
    then gather + per-query masked attend. Same 4-leaf cache donation
    as paged decode (lengths/block tables come back via the self-row
    rewrite, since verify leaves them numerically untouched)."""
    def build():
        from apex_tpu.serving.decode import (
            make_paged_verify_fn, make_tp_paged_verify_fn,
        )

        cfg = _serving_cfg()
        params, cache = _paged_serving_args(cfg)
        if tp is None:
            fn = make_paged_verify_fn(cfg)
        else:
            from apex_tpu.models.gpt import GPTModel

            fn = make_tp_paged_verify_fn(GPTModel(cfg, tp_size=tp))
        return fn, (params, cache, _sds((2, 4), "int32"))

    return build


def _spec_verify_step_medium_ragged_entry():
    """The verify step at the ragged medium shape (32 slots, bf16
    params, uniform 32..512 ladder), k+1 = 4 positions per slot —
    cost-tier only. Its budgets.json row divided by the expected
    committed tokens per slot at the measured acceptance rate is the
    bytes per accepted token, to set against the plain-decode
    ``model_bytes_per_token``."""
    def build():
        import functools as ft

        import jax
        import jax.numpy as jnp

        from apex_tpu.models.gpt import GPTConfig, init_gpt
        from apex_tpu.serving.cache import RESERVED_PAGES, init_paged_cache
        from apex_tpu.serving.decode import make_paged_verify_fn

        cfg = GPTConfig(use_rope=True)
        slots, s_max, page = 32, 512, 64
        lengths = [32 + round(i * (s_max - 32) / (slots - 1))
                   for i in range(slots)]
        num_pages = RESERVED_PAGES + sum(-(-l // page) for l in lengths)
        params = jax.eval_shape(
            lambda k: init_gpt(k, cfg, jnp.bfloat16), jax.random.PRNGKey(0))
        cache = jax.eval_shape(ft.partial(
            init_paged_cache, cfg, slots, s_max, num_pages, page))
        fn = make_paged_verify_fn(cfg)
        return fn, (params, cache, _sds((slots, 4), "int32"))

    return build


def _tree_verify_step_entry(tp=None):
    """Tree-attention verify: a k1 = 4-node draft grid per slot against
    the paged pool — the per-query linear mask of the spec verify
    replaced by the grid's ancestor-matrix columns. Same 4-leaf cache
    donation as the linear verify (lengths/block tables come back via
    the self-row rewrite)."""
    def build():
        from apex_tpu.serving.decode import (
            make_paged_tree_verify_fn, make_tp_paged_tree_verify_fn,
        )

        cfg = _serving_cfg()
        params, cache = _paged_serving_args(cfg)
        if tp is None:
            fn = make_paged_tree_verify_fn(cfg)
        else:
            from apex_tpu.models.gpt import GPTModel

            fn = make_tp_paged_tree_verify_fn(GPTModel(cfg, tp_size=tp))
        return fn, (params, cache, _sds((2, 4), "int32"),
                    _sds((2, 4), "int32"), _sds((2, 4, 4), "bool"))

    return build


def _draft_forward_step_entry():
    """The draft-forward anchor: ``draft_gpt_medium`` decoding one
    greedy token per slot through its lockstep cache (the drafter's own
    pool under its identity table) — 32 slots at the target's s_max =
    512 plus DraftModel's chunk = 5 catch-up headroom, bf16 params. Its budgets.json row is the ``draft_bytes``
    numerator of the model-draft break-even condition; the ceiling is
    hand-tightened to < 3% of the target's per-step parameter read
    (the ``gpt_paged_decode_step_medium_ragged`` row)."""
    def build():
        import functools as ft

        import jax
        import jax.numpy as jnp

        from apex_tpu.models.gpt import draft_gpt_medium, init_gpt
        from apex_tpu.serving.decode import make_paged_decode_fn
        from apex_tpu.serving.draft_model import init_draft_cache

        cfg = draft_gpt_medium()
        params = jax.eval_shape(
            lambda k: init_gpt(k, cfg, jnp.bfloat16), jax.random.PRNGKey(0))
        cache = jax.eval_shape(ft.partial(init_draft_cache, cfg, 32,
                                          512 + 5))
        fn = make_paged_decode_fn(cfg)
        return fn, (params, cache, _sds((32,), "int32"),
                    _sds((32,), "bool"))

    return build


def _w8_matmul_entry():
    """The dequant-fused int8 matmul family (column/row apply + the
    output-channel-major logits head) traced standalone — APX501 proves
    the fp32 accumulation survives into the jaxpr, APX503 that the
    register dequant never materializes a blown-up fp32 weight copy."""
    def build():
        from apex_tpu.quant.kernels import w8_matmul, w8_matmul_nk

        def fn(x, wq, scale, bias, tq, tscale):
            h = w8_matmul(x, wq, scale, bias, out_dtype=x.dtype)
            return w8_matmul_nk(h, tq, tscale)

        return fn, (_sds((8, 256), "bfloat16"),
                    _sds((256, 512), "int8"), _sds((512,), "float32"),
                    _sds((512,), "float32"),
                    _sds((1024, 512), "int8"), _sds((1024,), "float32"))

    return build


def _quant_paged_serving_args(cfg, num_slots=2, max_len=32, num_pages=6,
                              page_size=16):
    """Weight-only int8 params (same tree paths, int8 kernels + fp32
    scales) over an int8 page pool with per-page-per-head scales."""
    import functools as ft

    import jax
    import jax.numpy as jnp

    from apex_tpu.models.gpt import init_gpt
    from apex_tpu.quant.params import quantize_params
    from apex_tpu.serving.cache import init_paged_cache

    params = quantize_params(jax.eval_shape(
        lambda k: init_gpt(k, cfg), jax.random.PRNGKey(0)))
    cache = jax.eval_shape(ft.partial(
        init_paged_cache, cfg, num_slots, max_len, num_pages, page_size,
        jnp.int8))
    return params, cache


def _quant_paged_step_entry(which):
    """w8 + kv8 paged serving steps: the int8 pool donates SIX leaves
    (pool k/v, lengths, block tables, k/v scales) — min_alias_pairs=6
    pins the widened donation."""
    def build():
        from apex_tpu.serving.decode import (
            make_paged_decode_fn, make_paged_prefill_fn,
            make_paged_verify_fn,
        )

        cfg = _serving_cfg()
        params, cache = _quant_paged_serving_args(cfg)
        if which == "prefill":
            fn = make_paged_prefill_fn(cfg, quantized=True)
            return fn, (params, cache, _sds((1, 16), "int32"),
                        _sds((16,), "int32"), _sds((), "int32"),
                        _sds((1,), "int32"), _sds((2,), "int32"))
        if which == "verify":
            fn = make_paged_verify_fn(cfg, quantized=True)
            return fn, (params, cache, _sds((2, 4), "int32"))
        fn = make_paged_decode_fn(cfg, quantized=True)
        return fn, (params, cache, _sds((2,), "int32"),
                    _sds((2,), "bool"))

    return build


def _quant_paged_decode_medium_ragged_entry():
    """The quantized twin of the ragged medium paged decode: int8
    params (fp32 scales) + int8 page pool at the identical ladder —
    its budgets.json row pins the halved byte claim (≤ 0.95 GB/step vs
    1.68 GB bf16). Cost-tier only."""
    def build():
        import functools as ft

        import jax
        import jax.numpy as jnp

        from apex_tpu.models.gpt import GPTConfig, init_gpt
        from apex_tpu.quant.params import quantize_params
        from apex_tpu.serving.cache import RESERVED_PAGES, init_paged_cache
        from apex_tpu.serving.decode import make_paged_decode_fn

        cfg = GPTConfig(use_rope=True)
        slots, s_max, page = 32, 512, 64
        lengths = [32 + round(i * (s_max - 32) / (slots - 1))
                   for i in range(slots)]
        num_pages = RESERVED_PAGES + sum(-(-l // page) for l in lengths)
        params = quantize_params(jax.eval_shape(
            lambda k: init_gpt(k, cfg, jnp.bfloat16),
            jax.random.PRNGKey(0)))
        cache = jax.eval_shape(ft.partial(
            init_paged_cache, cfg, slots, s_max, num_pages, page,
            jnp.int8))
        fn = make_paged_decode_fn(cfg, quantized=True)
        return fn, (params, cache, _sds((slots,), "int32"),
                    _sds((slots,), "bool"))

    return build


def _fused_softmax_entry():
    """Both fused-softmax pallas families (masked 4D + causal 3D),
    fwd+bwd through the custom_vjp."""
    def build():
        import jax
        import jax.numpy as jnp

        from apex_tpu.transformer.functional import fused_softmax as fs

        def loss(x, mask, x3):
            y = fs.scaled_masked_softmax(x, mask, scale=0.5)
            z = fs.scaled_upper_triang_masked_softmax(x3, scale=0.5)
            return (jnp.sum(y.astype(jnp.float32) ** 2)
                    + jnp.sum(z.astype(jnp.float32) ** 2))

        fn = lambda *a: jax.value_and_grad(loss, (0, 2))(*a)
        return fn, (_sds((2, 2, 128, 128), "bfloat16"),
                    _sds((2, 1, 128, 128), "int32"),
                    _sds((4, 128, 128), "bfloat16"))

    return build


def _flat_simple_entry(which):
    """The three non-optimizer flat kernels (scale / axpby / l2norm):
    pure streaming, no input_output_aliases, so no aliases check."""
    rows = 8192

    def build():
        import functools as ft

        from apex_tpu.multi_tensor_apply import kernels as K

        buf = _sds((rows, 128), "float32")
        if which == "scale":
            return ft.partial(K.flat_scale, scale=0.5,
                              interpret=True), (buf,)
        if which == "axpby":
            return (lambda x, y: K.flat_axpby(1.0, x, 2.0, y,
                                              interpret=True)), (buf, buf)
        return ft.partial(K.flat_l2norm, interpret=True), (buf,)

    return build


def _local_shapes(tree, specs, axis_sizes):
    """TP-local ShapeDtypeStructs: divide each dim of each leaf by the
    product of the mesh-axis sizes its spec entry names (the shard a
    rank sees inside shard_map)."""
    import jax

    def one(leaf, spec):
        shape = list(leaf.shape)
        for dim, entry in enumerate(tuple(spec)):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for ax in axes:
                shape[dim] //= axis_sizes.get(ax, 1)
        return jax.ShapeDtypeStruct(tuple(shape), leaf.dtype)

    from jax.sharding import PartitionSpec as P

    return jax.tree_util.tree_map(one, tree, specs,
                                  is_leaf=lambda x: isinstance(x, P))


def zero_parts(dp: int = 2, tp: int = 2):
    """The ROADMAP item-3 headline config at a parametric mesh shape:
    rule-table-sharded GPT train step, dp x tp, ZeRO optimizer state
    (bf16 m) row-sharded over ``(model, data)`` jointly. Returns
    ``(fn, args, in_specs)`` — the spec tree is consumed by the APX7xx
    sharded tier (APX703 checks the shard_map in_specs against it), the
    ``(fn, args)`` pair by the plain trace/cost tiers, and the APX9xx
    scaling tier re-stages this builder at every swept ``(dp, tp)``
    shape. Everything sharded here derives from
    ``partition.gpt_rules()``; nothing is hand-specified — the caller's
    mesh must carry ``data`` axis size ``dp`` and ``model`` axis size
    ``tp``."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from apex_tpu.contrib.optimizers.distributed_fused_adam import (
        DistributedAdamState, DistributedFusedAdam,
    )
    from apex_tpu.models.gpt import GPTModel, gpt_tiny, init_gpt
    from apex_tpu.partition import gpt_rules, match_partition_rules
    from apex_tpu.transformer import parallel_state as ps

    cfg = gpt_tiny()
    model = GPTModel(cfg, tp_size=tp)
    params = jax.eval_shape(
        lambda k: init_gpt(k, cfg), jax.random.PRNGKey(0))
    specs = match_partition_rules(gpt_rules(), params)
    local_params = _local_shapes(params, specs, {ps.TENSOR_AXIS: tp})

    opt = DistributedFusedAdam(lr=1e-4, weight_decay=0.01, dp_size=dp,
                               m_dtype=jnp.bfloat16)
    # Flat ZeRO buffers are built from the TP-LOCAL param shard (each tp
    # rank optimizes only its own rows); at rest the global buffer
    # stacks the tp segments, hence leading rows tp * R_local and the
    # joint (model, data) row sharding from partition_spec().
    local_state = jax.eval_shape(opt.init, local_params)
    r_local = local_state.master.shape[0]
    state = DistributedAdamState(
        step=_sds((), local_state.step.dtype),
        master=_sds((tp * r_local, 128), local_state.master.dtype),
        m=_sds((tp * r_local, 128), local_state.m.dtype),
        v=_sds((tp * r_local, 128), local_state.v.dtype))
    zero_spec = opt.partition_spec(tensor_axis=ps.TENSOR_AXIS)

    def train_step(p, st, ids, labels):
        # local grads (check_vma=False): TP grads are already correct
        # per-shard, dp reduction happens in the optimizer's
        # psum_scatter; no separate DDP allreduce.
        loss, grads = jax.value_and_grad(model.loss)(p, ids, labels)
        new_p, new_st = opt.step(grads, p, st)
        return lax.pmean(loss, ps.DATA_AXIS), new_p, new_st

    in_specs = (specs, zero_spec, P(ps.DATA_AXIS), P(ps.DATA_AXIS))
    fn = ps.shard_map(train_step, in_specs=in_specs,
                      out_specs=(P(), specs, zero_spec))
    args = (params, state, _sds((2 * dp, 32), "int32"),
            _sds((2 * dp, 32), "int32"))
    return fn, args, in_specs


def zero_dp2xtp2_parts():
    """The dp2 x tp2 anchor shape of :func:`zero_parts` (the original
    ROADMAP item-3 headline config)."""
    return zero_parts(dp=2, tp=2)


def _zero_entry(dp, tp):
    def build():
        fn, args, _ = zero_parts(dp=dp, tp=tp)
        return fn, args

    return build


def _mesh(pp=1, vpp=None, tp=1, cp=1, n_devices=None):
    def setup():
        import jax

        from apex_tpu.transformer import parallel_state as ps

        devs = jax.devices()
        if n_devices is not None:
            devs = devs[:n_devices]
        ps.initialize_model_parallel(
            tensor_model_parallel_size_=tp,
            pipeline_model_parallel_size_=pp,
            virtual_pipeline_model_parallel_size_=vpp,
            context_parallel_size_=cp,
            devices=devs)

    return setup


def repo_entries() -> List[TraceEntry]:
    flash = "apex_tpu.transformer.functional.flash_attention"
    ln = "apex_tpu.normalization.fused_layer_norm"
    flat = "apex_tpu.multi_tensor_apply.kernels"
    sched = "apex_tpu.transformer.pipeline_parallel.schedules"
    entries = [
        TraceEntry("flash_d64_bf16_s512_fwd_bwd", flash,
                   _flash_entry(64, "bfloat16", 512)),
        TraceEntry("flash_d128_f32_s512_fwd_bwd", flash,
                   _flash_entry(128, "float32", 512)),
        TraceEntry("fmha_d64_bf16_s128_fwd_bwd", flash, _fmha_entry()),
        TraceEntry("ln_h1024_fwd_bwd", ln, _ln_entry(1024)),
        TraceEntry("rms_h4096_fwd_bwd", ln, _ln_entry(4096, rms=True)),
        TraceEntry("xentropy_fwd_bwd", "apex_tpu.contrib.xentropy",
                   _xentropy_entry()),
        TraceEntry("flat_adam", flat, _flat_entry("adam"),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=3),
        TraceEntry("flat_sgd", flat, _flat_entry("sgd"),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=2),
        TraceEntry("flat_lamb", flat, _flat_entry("lamb"),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=2),
        TraceEntry("flat_adagrad", flat, _flat_entry("adagrad"),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=2),
        TraceEntry("flat_novograd", flat, _flat_entry("novograd"),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=2),
        # tree path is per-leaf XLA math (no pallas kernels), so there
        # is deliberately no aliases check here — the flat_* entries
        # above carry the APX512 coverage
        TraceEntry("fused_adam_tree_step",
                   "apex_tpu.optimizers.fused_adam",
                   _fused_adam_tree_entry()),
        TraceEntry("amp_o2_bert_step", "apex_tpu.amp.frontend",
                   _amp_o2_step_entry("bert"),
                   checks=("precision", "amp", "memory")),
        TraceEntry("amp_o2_gpt_step", "apex_tpu.amp.frontend",
                   _amp_o2_step_entry("gpt"),
                   checks=("precision", "amp", "memory")),
        TraceEntry("tp_block_tp2", "apex_tpu.transformer.tensor_parallel",
                   _tp_block_entry(2),
                   checks=("precision", "memory", "schedule"),
                   mesh=_mesh(tp=2), min_devices=2),
        TraceEntry("pp_1f1b_pp4", sched, _pp_1f1b_entry(4, 8),
                   checks=("precision", "memory", "schedule"),
                   mesh=_mesh(pp=4, n_devices=4), min_devices=4),
        TraceEntry("pp_interleaved_pp2_vpp2", sched,
                   _pp_interleaved_entry(2, 2, 4),
                   checks=("precision", "memory", "schedule"),
                   mesh=_mesh(pp=2, vpp=2, n_devices=2), min_devices=2),
        TraceEntry("pp_no_pipelining_fp32_accum", sched,
                   _pp_sequential_entry()),
        # ROADMAP item 3 headline: dp2 x tp2 ZeRO train step, every
        # sharding derived from partition.gpt_rules(); the APX7xx tier
        # re-traces the same builder for its in_specs/schedule checks
        TraceEntry("gpt_tiny_dp2xtp2_zero",
                   "apex_tpu.contrib.optimizers.distributed_fused_adam",
                   _zero_entry(2, 2),
                   checks=("precision", "memory", "schedule"),
                   mesh=_mesh(tp=2, n_devices=4), min_devices=4),
        # ROADMAP item 5 payoff: the same rule-derived ZeRO step at the
        # dp4 x tp2 headline shape (the full 8-device world) — the
        # APX9xx scaling tier sweeps the builder across the whole
        # (dp, tp) grid; this entry pins the headline shape in the
        # APX5xx/6xx tiers too, with its own budgets.json row
        TraceEntry("gpt_tiny_dp4xtp2_zero",
                   "apex_tpu.contrib.optimizers.distributed_fused_adam",
                   _zero_entry(4, 2),
                   checks=("precision", "memory", "schedule"),
                   mesh=_mesh(tp=2, n_devices=8), min_devices=8),
        TraceEntry("bottleneck_spatial_cp2",
                   "apex_tpu.contrib.bottleneck.bottleneck",
                   _bottleneck_entry(),
                   checks=("precision", "memory", "schedule"),
                   mesh=_mesh(cp=2, n_devices=2), min_devices=2),
        # serving: the KV cache (pool k/v, lengths, block tables) is
        # DONATED into every jitted step — min_alias_pairs=4 pins the
        # whole-cache donation (APX512's pjit branch); a dropped
        # donate_argnums re-allocates the whole pool every decoded token
        TraceEntry("gpt_paged_prefill_step", "apex_tpu.serving.decode",
                   _paged_prefill_step_entry(),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=4),
        # chunked prefill: the same donation as the monolithic step — a
        # dropped pair would re-allocate the whole cache EVERY CHUNK,
        # multiplying the admission cost by the chunk count
        TraceEntry("gpt_paged_chunk_prefill_step",
                   "apex_tpu.serving.decode",
                   _paged_chunk_prefill_step_entry(),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=4),
        TraceEntry("gpt_paged_decode_step", "apex_tpu.serving.decode",
                   _paged_decode_step_entry(),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=4),
        TraceEntry("hybrid_prefill_step",
                   "apex_tpu.transformer.functional.gated_delta",
                   _model_step_entry("hybrid", "prefill"),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=6),
        TraceEntry("hybrid_decode_step",
                   "apex_tpu.transformer.functional.gated_delta",
                   _model_step_entry("hybrid", "decode"),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=6),
        TraceEntry("nemotron_h_prefill_step",
                   "apex_tpu.transformer.functional.moe",
                   _model_step_entry("nemotron_h", "prefill"),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=9),
        TraceEntry("nemotron_h_decode_step",
                   "apex_tpu.transformer.functional.ssd",
                   _model_step_entry("nemotron_h", "decode"),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=9),
        TraceEntry("deepseek_prefill_step",
                   "apex_tpu.models.deepseek",
                   _model_step_entry("deepseek", "prefill"),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=6),
        TraceEntry("deepseek_decode_step",
                   "apex_tpu.transformer.functional.mla_attention",
                   _model_step_entry("deepseek", "decode"),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=6),
        TraceEntry("exaone_prefill_step",
                   "apex_tpu.models.exaone_moe",
                   _model_step_entry("exaone_moe", "prefill"),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=9),
        TraceEntry("exaone_decode_step",
                   "apex_tpu.transformer.functional.paged_attention",
                   _model_step_entry("exaone_moe", "decode"),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=9),
        TraceEntry("ling_prefill_step",
                   "apex_tpu.models.bailing_hybrid",
                   _model_step_entry("ling", "prefill"),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=8),
        TraceEntry("ling_decode_step",
                   "apex_tpu.transformer.functional.gated_delta",
                   _model_step_entry("ling", "decode"),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=8),
        TraceEntry("glm_prefill_step",
                   "apex_tpu.models.glm_next",
                   _model_step_entry("glm", "prefill"),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=12),
        TraceEntry("glm_decode_step",
                   "apex_tpu.transformer.functional.sparse_index",
                   _model_step_entry("glm", "decode"),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=12),
        TraceEntry("gpt_paged_decode_step_tp2", "apex_tpu.serving.decode",
                   _paged_decode_step_entry(tp=2),
                   checks=("precision", "memory", "schedule", "aliases"),
                   mesh=_mesh(tp=2), min_devices=2, min_alias_pairs=4),
        # speculative verify: same donated 4-leaf paged cache as the
        # decode step, k+1 query positions per slot
        TraceEntry("gpt_spec_verify_step", "apex_tpu.serving.decode",
                   _spec_verify_step_entry(),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=4),
        TraceEntry("gpt_spec_verify_step_tp2", "apex_tpu.serving.decode",
                   _spec_verify_step_entry(tp=2),
                   checks=("precision", "memory", "schedule", "aliases"),
                   mesh=_mesh(tp=2), min_devices=2, min_alias_pairs=4),
        # tree-attention verify: one forward over a k1-node draft grid
        # per slot (ancestor-matrix mask in place of the linear one);
        # the donated 4-leaf paged cache is unchanged
        TraceEntry("gpt_tree_verify_step", "apex_tpu.serving.decode",
                   _tree_verify_step_entry(),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=4),
        TraceEntry("gpt_tree_verify_step_tp2", "apex_tpu.serving.decode",
                   _tree_verify_step_entry(tp=2),
                   checks=("precision", "memory", "schedule", "aliases"),
                   mesh=_mesh(tp=2), min_devices=2, min_alias_pairs=4),
        # cost-tier anchor for the decode roofline, a ragged-length
        # pool at the medium model shape; no APX5xx checks (the
        # tiny-shape decode entries above carry them — this one exists so
        # budgets.json pins the headline bytes)
        # (the step whose attention is the paged-attention kernel: this
        # entry is that kernel family's registration)
        TraceEntry("gpt_paged_decode_step_medium_ragged",
                   "apex_tpu.transformer.functional.paged_attention",
                   _paged_decode_step_medium_ragged_entry(), checks=()),
        # the verify step at the same ragged shape — one parameter
        # read priced over k+1 candidate positions; budgets.json pins
        # the bytes per accepted token
        TraceEntry("gpt_spec_verify_step_medium_ragged",
                   "apex_tpu.serving.decode",
                   _spec_verify_step_medium_ragged_entry(), checks=()),
        # r14: one chunk of a chunked prefill at the same ragged
        # medium shape — budgets.json pins the per-chunk HBM bytes
        # (~chunk/S of the monolithic read plus the cache re-read)
        TraceEntry("gpt_paged_chunk_prefill_step_medium",
                   "apex_tpu.serving.decode",
                   _paged_chunk_prefill_step_medium_entry(), checks=()),
        # r15: the disaggregated handoff's receiver scatter at the same
        # ragged medium shape — budgets.json pins the per-prompt-page
        # handoff bytes the router ships between replicas
        TraceEntry("gpt_page_handoff_medium",
                   "apex_tpu.serving.transfer",
                   _page_handoff_medium_entry(), checks=()),
        # r17: the reshard tier's sender collective at the same ragged
        # medium shape — the explicit tiled all_gather over the tp=2
        # model axis that APX511's per-rank simulator verifies and
        # budgets.json prices as the per-prompt ICI/DCN collective
        # volume behind ici_ticks_per_page / dcn_ticks_per_page
        TraceEntry("gpt_page_reshard_medium",
                   "apex_tpu.serving.transfer",
                   _page_reshard_medium_entry(),
                   checks=("schedule",),
                   mesh=_mesh(tp=2), min_devices=2),
        # r16: the KV-cache hierarchy's two data movers at the same
        # ragged medium shape — the spill-side page gather (bf16) and
        # the promote-side quantized scatter (int8 + scale planes);
        # budgets.json pins the per-page bytes a spill/promote moves,
        # the denominator behind promote_ticks_per_page
        TraceEntry("gpt_page_spill_extract_medium",
                   "apex_tpu.serving.transfer",
                   _page_spill_extract_medium_entry(), checks=()),
        TraceEntry("gpt_page_promote_insert_quant_medium",
                   "apex_tpu.serving.transfer",
                   _page_promote_insert_quant_medium_entry(),
                   checks=()),
        # the model drafter's per-token forward at the medium
        # shape — the draft_bytes numerator of the break-even condition
        # (docs/source/serving.rst); its hand-tightened ceiling pins the draft
        # under 3% of the target parameter read. The drafter's cache
        # donation (4 leaves) rides along.
        TraceEntry("gpt_draft_forward_step",
                   "apex_tpu.serving.draft_model",
                   _draft_forward_step_entry(),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=4),
        # int8 tier: the standalone dequant-fused matmuls, the w8+kv8
        # paged serving steps (6 donated cache leaves — pool k/v,
        # lengths, block tables, k/v scales), and the r12 cost anchor at
        # the ragged medium shape
        TraceEntry("w8_matmul_fused", "apex_tpu.quant.kernels",
                   _w8_matmul_entry()),
        TraceEntry("gpt_paged_prefill_step_w8kv8",
                   "apex_tpu.serving.decode",
                   _quant_paged_step_entry("prefill"),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=6),
        TraceEntry("gpt_paged_decode_step_w8kv8",
                   "apex_tpu.serving.decode",
                   _quant_paged_step_entry("decode"),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=6),
        TraceEntry("gpt_spec_verify_step_w8kv8",
                   "apex_tpu.serving.decode",
                   _quant_paged_step_entry("verify"),
                   checks=("precision", "memory", "aliases"),
                   min_alias_pairs=6),
        TraceEntry("gpt_paged_decode_step_medium_ragged_w8kv8",
                   "apex_tpu.serving.decode",
                   _quant_paged_decode_medium_ragged_entry(), checks=()),
        TraceEntry("fused_softmax_fwd_bwd",
                   "apex_tpu.transformer.functional.fused_softmax",
                   _fused_softmax_entry()),
        TraceEntry("flat_scale", flat, _flat_simple_entry("scale")),
        TraceEntry("flat_axpby", flat, _flat_simple_entry("axpby")),
        TraceEntry("flat_l2norm", flat, _flat_simple_entry("l2norm")),
    ]
    return entries


def check_repo() -> List[Finding]:
    return run_entries(repo_entries())
