"""sha256 of lowered text of the tiny configs' prefill/decode programs."""
import hashlib, sys, os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax, jax.numpy as jnp
from apex_tpu.serving import PagedDecodeEngine
from apex_tpu.models import gpt as gptm, hybrid, nemotron_h, deepseek, exaone_moe
def engines():
    k = jax.random.PRNGKey(0)
    cfg = gptm.gpt_tiny() if hasattr(gptm, "gpt_tiny") else None
    out = {}
    if cfg is not None:
        from apex_tpu.models.gpt import init_gpt as init_gpt_params
        import dataclasses
        cfg = dataclasses.replace(cfg, use_rope=True, hidden_dropout=0.0)
        out["gpt"] = (init_gpt_params(k, cfg), cfg, {})
    c = hybrid.hybrid_tiny(); out["hybrid"] = (hybrid.init_hybrid(k, c), c, dict(prefix_sharing=False, cache_dtype=jnp.float32))
    c = nemotron_h.nemotron_h_tiny(); out["nemotron"] = (nemotron_h.init(k, c), c, dict(prefix_sharing=False, cache_dtype=jnp.float32))
    c = deepseek.deepseek_tiny(); out["deepseek"] = (deepseek.init(k, c), c, dict(cache_dtype=jnp.float32))
    c = exaone_moe.exaone_moe_tiny(); out["exaone"] = (exaone_moe.init(k, c), c, dict(cache_dtype=jnp.float32))
    return out
for name, (params, cfg, kw) in engines().items():
    for dt in ([jnp.float32, jnp.bfloat16] if name != "gpt" else [jnp.bfloat16, jnp.float32]):
        kw2 = {**kw, "cache_dtype": dt}
        eng = PagedDecodeEngine(params, cfg, num_slots=3, max_len=512, num_pages=100, page_size=16, buckets=[64, 512], **kw2)
        for pname, tr in eng.trace_programs().items():
            print(name, jnp.dtype(dt).name, pname, hashlib.sha256(tr.lower().as_text().encode()).hexdigest()[:16])
