"""Profile the llama-3-8B int8 decode step on the real chip.

Isolates: full fused decode step, weight-stream floor (attention patched to
identity) and XLA-attention variant — all measured INSIDE decode_multi (isolated kernel timings don't transfer).
"""
import functools
import os, sys, time
import numpy as np
import jax, jax.numpy as jnp

from dynamo_tpu.utils.jax_env import configure_compile_cache

configure_compile_cache()

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import llama3_8b_config
from dynamo_tpu.models.quantize import init_quantized_params, quantize_params
from dynamo_tpu.ops import attention as attn_mod

cfg = llama3_8b_config()
print("backend", jax.default_backend(), flush=True)

B = int(os.environ.get("PB", 64))
BS = int(os.environ.get("PBS", 128))
CTX = int(os.environ.get("PCTX", 160))
P = (CTX + 1 + BS - 1) // BS  # pages needed for pos=CTX
NB = max(B * P + 8, 192 * 128 // BS)
STEPS = int(os.environ.get("PSTEPS", 16))

params = init_quantized_params(cfg, 0)
axes = llama.param_logical_axes(cfg)
params, _ = quantize_params(params, axes)
KVQ = os.environ.get("PKV") or None
k, v = llama.init_kv_cache(cfg, NB, BS, layered=True, kv_dtype=KVQ)
rng0 = np.random.default_rng(0)
tables = jnp.asarray(
    rng0.permutation(NB)[: B * P].reshape(B, P).astype(np.int32)
)
tok = jnp.ones((B,), jnp.int32)
pos = jnp.full((B,), CTX, jnp.int32)
act = jnp.ones((B,), jnp.int32)
rng = jax.random.PRNGKey(1)
temp = jnp.ones((B,), jnp.float32)
topk = jnp.zeros((B,), jnp.int32)
topp = jnp.full((B,), 0.95, jnp.float32)


def mkdec(use_kernel):
    def f(p_, k_, v_):
        return llama.decode_multi(
            p_, cfg, tok, pos, act, tables, k_, v_, rng, temp, topk, topp,
            num_steps=STEPS, use_kernel=use_kernel, want_logprobs=False,
        )
    return jax.jit(f, donate_argnums=(1, 2))


def bench(label, fn, n=3):
    global k, v
    out = fn(params, k, v)
    k, v = out[-2], out[-1]
    _ = np.asarray(out[0])  # force readback
    ts = []
    for _i in range(n):
        t0 = time.perf_counter()
        out = fn(params, k, v)
        k, v = out[-2], out[-1]
        _ = np.asarray(out[0])
        ts.append(time.perf_counter() - t0)
    dt = min(ts)
    print(
        f"{label}: {dt*1000:.1f} ms total, {dt/STEPS*1000:.2f} ms/step "
        f"-> {B*STEPS/dt:.0f} tok/s",
        flush=True,
    )
    return dt


which = sys.argv[1:] if len(sys.argv) > 1 else ["full", "floor"]

if "full" in which:
    bench(f"decode kernel (B={B} bs={BS} P={P} ctx={CTX})", mkdec(True))

if "floor" in which:
    real = llama.paged_attention
    llama.paged_attention = lambda q, *a, **kw: q
    bench("decode NO-ATTENTION floor", mkdec(True))
    llama.paged_attention = real

if "xla" in which:
    bench("decode XLA attention", mkdec(False))

if "nowrite" in which:
    real_a, real_w = llama.paged_attention, llama.write_chunk_to_cache
    llama.paged_attention = lambda q, *a, **kw: q
    llama.write_chunk_to_cache = lambda c, *a, **kw: c
    bench("decode NO-ATTN NO-CACHE-WRITE", mkdec(True))
    llama.paged_attention, llama.write_chunk_to_cache = real_a, real_w

if "nohead" in which:
    import dynamo_tpu.models.llama as lm
    real_a, real_w = llama.paged_attention, llama.write_chunk_to_cache
    real_h = llama.lm_head_logits
    llama.paged_attention = lambda q, *a, **kw: q
    llama.write_chunk_to_cache = lambda c, *a, **kw: c
    llama.lm_head_logits = lambda p_, c_, x: jnp.zeros(
        (x.shape[0], c_.vocab_size), jnp.bfloat16
    ) + x[:, :1].astype(jnp.bfloat16)
    bench("decode NO-ATTN NO-WRITE NO-LMHEAD", mkdec(True))
    llama.paged_attention, llama.write_chunk_to_cache = real_a, real_w
    llama.lm_head_logits = real_h

if "mm" in which:
    from dynamo_tpu.ops.quant import qeinsum

    lw = params["layers"]

    def mm_chain(p_, x):
        for l in range(cfg.n_layers):
            lp_l = jax.tree.map(lambda a, _l=l: a[_l], p_["layers"])
            q_ = qeinsum("bd,dh->bh", x, lp_l["wq"])
            k_ = qeinsum("bd,dh->bh", x, lp_l["wk"])
            v_ = qeinsum("bd,dh->bh", x, lp_l["wv"])
            o_ = qeinsum("bd,dh->bh", q_, lp_l["wo"])
            g_ = qeinsum("bd,df->bf", x, lp_l["w_gate"])
            u_ = qeinsum("bd,df->bf", x, lp_l["w_up"])
            d_ = qeinsum("bf,fd->bd", g_ * u_, lp_l["w_down"])
            # keep every matmul live without changing x's scale
            x = x + 1e-6 * o_ + 1e-6 * d_ + 1e-6 * (k_.sum() + v_.sum())
        return x

    def steps_fn(p_, x):
        def one(c, _):
            return mm_chain(p_, c), ()
        y, _ = jax.lax.scan(one, x, None, length=STEPS)
        return y

    f = jax.jit(steps_fn)
    x0 = jnp.ones((B, cfg.d_model), jnp.bfloat16)
    _ = np.asarray(f(params, x0))
    ts = []
    for _i in range(3):
        t0 = time.perf_counter()
        _ = np.asarray(f(params, x0))
        ts.append(time.perf_counter() - t0)
    dt = min(ts)
    print(
        f"pure int8 matmul chain: {dt*1000:.1f} ms total, "
        f"{dt/STEPS*1000:.2f} ms/step",
        flush=True,
    )

if "v2" in which:
    from _prof_attn import decode_packed

    real = llama.paged_attention

    def patched_v2(q, k_c, v_c, bt, sp, cl, *, use_kernel, sm_scale, window,
                   logit_cap, **_):
        return decode_packed(
            q, k_c, v_c, bt, sp, window, sm_scale=sm_scale,
            logit_cap=logit_cap,
        )

    llama.paged_attention = patched_v2
    bench("decode V2 PACKED kernel", mkdec(True))
    llama.paged_attention = real

if "bf" in which:
    from _prof_attn import decode_bf16

    real = llama.paged_attention

    def patched_bf(q, k_c, v_c, bt, sp, cl, *, use_kernel, sm_scale, window,
                   logit_cap, **_):
        return decode_bf16(
            q, k_c, v_c, bt, sp, window, sm_scale=sm_scale,
            logit_cap=logit_cap,
        )

    llama.paged_attention = patched_bf
    bench("decode V1-BF16-OPERANDS kernel", mkdec(True))
    llama.paged_attention = real

if "nosample" in which:
    import dynamo_tpu.ops.sampling as smp

    real_s = smp.sample_tokens

    def cheap_sample(logits, rng_, temperature, top_k, top_p, min_p=None):
        # cheapest data-dependent reduction: single max over vocab
        return jnp.argmax(logits[:, :128], axis=-1).astype(jnp.int32)

    smp.sample_tokens = cheap_sample
    bench("decode CHEAP-SAMPLE (full attn+head)", mkdec(True))
    smp.sample_tokens = real_s

if "head" in which:
    from dynamo_tpu.ops.sampling import sample_tokens

    x0 = jnp.ones((B, cfg.d_model), jnp.bfloat16)

    def head_only(p_, x):
        def one(c, _):
            lg = llama.lm_head_logits(p_, cfg, x + c[:, None].astype(jnp.bfloat16))
            return lg.sum(-1).astype(jnp.float32), ()
        y, _ = jax.lax.scan(
            one, jnp.zeros((B,), jnp.float32), None, length=STEPS
        )
        return y

    def head_sample(p_, x):
        def one(c, r):
            lg = llama.lm_head_logits(p_, cfg, x + c[:, None].astype(jnp.bfloat16))
            t = sample_tokens(lg, r, temp, topk, topp)
            return t.astype(jnp.float32), ()
        y, _ = jax.lax.scan(
            one, jnp.zeros((B,), jnp.float32),
            jax.random.split(rng, STEPS),
        )
        return y

    def sample_only(lg):
        def one(c, r):
            t = sample_tokens(lg + c[:, None], r, temp, topk, topp)
            return t.astype(jnp.float32), ()
        y, _ = jax.lax.scan(
            one, jnp.zeros((B,), jnp.float32), jax.random.split(rng, STEPS)
        )
        return y

    for label, f, a in (
        ("lm_head only", jax.jit(head_only), (params, x0)),
        ("lm_head+sample", jax.jit(head_sample), (params, x0)),
        ("sample only", jax.jit(sample_only),
         (jnp.ones((B, cfg.vocab_size), jnp.float32),)),
    ):
        _ = np.asarray(f(*a))
        ts = []
        for _i in range(3):
            t0 = time.perf_counter()
            _ = np.asarray(f(*a))
            ts.append(time.perf_counter() - t0)
        print(f"{label}: {min(ts)/STEPS*1000:.2f} ms/step", flush=True)
