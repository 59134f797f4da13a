"""Plain reference of the ``laguna`` forward pass (tier-1's copy).

``benchmark/references/laguna-xs.2-pp8.py`` holds the same text between its
``reference: begin`` / ``reference: end`` markers, so that a PR which changes
the program cannot change what the benchmark compares it with;
``tests/test_swa.py`` checks that the two agree.
"""

# --- reference: begin ---------------------------------------------------------
# The forward pass of a cut laguna model in straightforward jax.numpy: float32,
# matmuls at "highest" precision, ONE sequence at a time, no cache, no kernels,
# no batching, every mask built from positions. The experts are a loop over
# the experts. Weights are converted to float32 one sublayer (one expert) at a
# time, and attention runs in groups of K/V heads and blocks of query
# positions (``kv_group``, ``query_block``: the result does not depend on
# them), so the published widths fit beside the program under test at 32 k
# tokens.
#
# Assumed, where the published config names a switch and not its shape (the
# configuration's file lists the same under ``assumed``):
#   1. ``gating: true`` is a sigmoid gate PER HEAD, computed from the
#      sublayer's normed input: o_h <- sigmoid(N1 x . w_g)_h * o_h, w_g
#      [d, heads of that layer], before W_o (a per-lane gate would add 0.63 B
#      parameters to the published 33.4 B; per head the count comes out);
#   2. the router scores with a sigmoid (no scoring key in the config): the
#      ``top_k`` largest scores choose, and, normalised over the chosen,
#      times ``moe_routed_scaling_factor``, weigh the experts' outputs; no
#      correction bias, no groups, float32 router;
#   3. no query/key norm (no key for one), and the dense layer 0 has no
#      shared expert.
# Also: the rotary lanes pair lane i with lane i + rotary/2 (the repo's
# ``rotate_half`` layout; with seeded random weights an interleaved pairing is
# a relabelling); a full layer rotates its first ``rotary`` lanes only, with
# YaRN's blended frequencies and cos and sin times the attention factor.
#   * ``degrade``: None is the reference. "softmax_bf16" rounds scores,
#     probabilities and their sums to bfloat16; "kv_int8" rounds each token's
#     K and V rows to 8 bits with one scale a head; "window_511" / "window_513"
#     move the sliding window by one; "rope_all_lanes" rotates all lanes of a
#     full layer (its frequencies over the whole head); "no_gate" leaves the
#     output gate out: each exists to show what a lower precision or a wrong
#     law reads against each limit.
import math

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _bf16_round(a):  # (a cast pair would be optimised away)
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def ref_rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(w)


def ref_rope_freqs(rotary, theta, yarn):
    """[rotary // 2] frequencies; ``yarn`` = (factor, original positions,
    beta_fast, beta_slow) or None."""
    inv = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary))
    if yarn is None:
        return inv
    factor, original, beta_fast, beta_slow = yarn
    dim = lambda rot: rotary * math.log(original / (rot * 2 * math.pi)) / (2 * math.log(theta))
    low, high = max(math.floor(dim(beta_fast)), 0), min(math.ceil(dim(beta_slow)), rotary - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(rotary // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return inv / factor * ramp + inv * (1.0 - ramp)


def ref_rope(x, L, degrade=None):
    """x [T, H, D] at positions 0..T-1: the first ``rotary`` lanes rotate."""
    T, D = x.shape[0], x.shape[-1]
    rotary = D if degrade == "rope_all_lanes" else L["rotary"]
    freqs = ref_rope_freqs(rotary, L["theta"], L["yarn"])
    ang = (jnp.arange(T, dtype=jnp.float32)[:, None] * freqs)[:, None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1) * L["attention_factor"]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1) * L["attention_factor"]
    head, rest = x[..., :rotary], x[..., rotary:]
    rot = jnp.concatenate([-head[..., rotary // 2:], head[..., : rotary // 2]], -1)
    return jnp.concatenate([head * cos + rot * sin, rest], -1)


def _int8_rows(a):  # [T, KH, D]: one scale a token a head
    scale = jnp.max(jnp.abs(a), -1, keepdims=True) / 127.0 + 1e-30
    return jnp.round(a / scale) * scale


def ref_attention(x, w, L, degrade=None, kv_group=None, query_block=None, want_heads=False):
    """Causal (and, where ``window``, sliding) attention with the per-head
    output gate. x [T, d] -> [T, d] (or, ``want_heads``, the gated per-head
    output [T, H, D] before W_o)."""
    T = x.shape[0]
    H, KH, D, W = L["heads"], L["kv_heads"], L["head_dim"], L["window"]
    if W and degrade in ("window_511", "window_513"):
        W = W + (1 if degrade == "window_513" else -1)
    G, QB, Q = kv_group or KH, query_block or T, H // KH
    assert T % QB == 0 and KH % G == 0, (T, QB, KH, G)
    low = _bf16_round if degrade == "softmax_bf16" else (lambda a: a)
    q = ref_rope((x @ _f32(w["wq"])).reshape(T, H, D), L, degrade if not L["window"] else None)
    k = ref_rope((x @ _f32(w["wk"])).reshape(T, KH, D), L, degrade if not L["window"] else None)
    v = (x @ _f32(w["wv"])).reshape(T, KH, D)
    if degrade == "kv_int8":
        k, v = _int8_rows(k), _int8_rows(v)
    # A query block sees keys from its first query's window on: K and V are
    # cut to that span (``span`` keys ending at the block's last query).
    span = min(T, QB + W) if W else T
    pad = span - QB
    k_pad = jnp.concatenate([jnp.zeros((pad, KH, D), jnp.float32), k], 0)
    v_pad = jnp.concatenate([jnp.zeros((pad, KH, D), jnp.float32), v], 0)
    heads = []
    for g0 in range(0, KH, G):  # a group of K/V heads at a time
        qg = q.reshape(T, KH, Q, D)[:, g0 : g0 + G]

        def block(r0):  # QB query positions from r0 against ``span`` keys
            qb = jax.lax.dynamic_slice_in_dim(qg, r0, QB)
            kb = jax.lax.dynamic_slice_in_dim(k_pad, r0, span)[:, g0 : g0 + G]
            vb = jax.lax.dynamic_slice_in_dim(v_pad, r0, span)[:, g0 : g0 + G]
            s = low(jnp.einsum("qgnd,tgd->gnqt", qb, kb) * D**-0.5)
            t_pos = r0 - pad + jnp.arange(span)[None, :]  # key positions
            q_pos = r0 + jnp.arange(QB)[:, None]
            seen = (t_pos >= 0) & (t_pos <= q_pos)
            if W:
                seen = seen & (t_pos > q_pos - W)
            s = jnp.where(seen[None, None], s, -jnp.inf)
            p = low(jnp.exp(s - s.max(-1, keepdims=True)))
            p = low(p / low(p.sum(-1, keepdims=True)))
            return low(jnp.einsum("gnqt,tgd->qgnd", p, vb))

        heads.append(jax.lax.map(block, jnp.arange(0, T, QB)).reshape(T, G * Q, D))
    o = jnp.concatenate(heads, 1)  # [T, H, D]
    if L["gate"] and degrade != "no_gate":
        o = o * jax.nn.sigmoid(x @ _f32(w["w_gate_attn"]))[..., None]
    if want_heads:
        return o
    return o.reshape(T, H * D) @ _f32(w["wo"])


def ref_dense_ffn(x, w, L):
    """Gated-silu FFN. x [T, d] -> [T, d]."""
    return (jax.nn.silu(x @ _f32(w["w_gate"])) * (x @ _f32(w["w_up"]))) @ _f32(w["w_down"])


def ref_route(x, w, L):
    """(chosen expert ids [T, k], their weights [T, k], the margin [T]
    between the last chosen and the first not chosen score)."""
    s = jax.nn.sigmoid(x @ _f32(w["router_w"]))
    top, idx = jax.lax.top_k(s, L["top_k"] + 1)
    wt = top[:, : L["top_k"]]
    wt = wt / (wt.sum(-1, keepdims=True) + 1e-20) * L["scale"]
    return idx[:, : L["top_k"]], wt, top[:, L["top_k"] - 1] - top[:, L["top_k"]]


def ref_experts(x, w, L):
    """x [T, d] -> out [T, d]: the routed experts' weighted outputs + the
    shared expert's."""
    idx, wt, _ = ref_route(x, w, L)
    ffn = lambda gate, up, down: (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)

    def expert(out, e):  # the loop over the experts, one at a time
        e_id, gate, up, down = e
        share = jnp.where(idx == e_id, wt, 0.0).sum(-1)  # [T], 0 where not chosen
        return out + share[:, None] * ffn(gate, up, down), None

    shared = ffn(w["ws_gate"], w["ws_up"], w["ws_down"])
    out, _ = jax.lax.scan(
        expert, shared,
        (jnp.arange(w["we_up"].shape[0]), w["we_gate"], w["we_up"], w["we_down"]))
    return out


def reference_forward(weights, layers, tokens, eps, positions=None, degrade=None,
                      kv_group=None, query_block=None, attention_of=()):
    """tokens [T] -> {"logits" [n, V] at ``positions`` (default: all),
    "hidden": the input of every sublayer at ``positions`` [n, d], "final":
    the residual stream after the last sublayer at ``positions``,
    "attention": for each sublayer index in ``attention_of`` its gated
    per-head attention output [n, H, D]}."""
    keep = jnp.arange(len(tokens)) if positions is None else jnp.asarray(positions)
    with jax.default_matmul_precision("highest"):
        h = _f32(weights["embed"][jnp.asarray(tokens)])
        hidden, attention = [], {}
        for i, (w, L) in enumerate(zip(weights["layers"], layers)):
            hidden.append(h[keep])
            if i in attention_of:
                attention[i] = _ATTENTION_HEADS(
                    h, w, _Static(L), eps, degrade, kv_group, query_block)[keep]
            h = _SUBLAYER(h, w, _Static(L), eps, degrade, kv_group, query_block)
        return {"logits": _head(h[keep], weights["final_norm"], weights["lm_head"], eps),
                "hidden": hidden, "final": h[keep], "attention": attention}


class _Static(dict):
    """A sublayer description as a static (hashable) argument: one compiled
    function per sublayer kind and sequence length, not one per call."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _sublayer(h, w, L, eps, degrade, kv_group, query_block):
    """h <- h + F(N(h))."""
    with jax.default_matmul_precision("highest"):
        x = ref_rmsnorm(h, w["norm"], eps)
        if L["kind"] == "attention":
            out = ref_attention(x, w, L, degrade, kv_group, query_block)
        elif L["kind"] == "dense_ffn":
            out = ref_dense_ffn(x, w, L)
        else:
            out = ref_experts(x, w, L)
        return h + out


def _attention_heads(h, w, L, eps, degrade, kv_group, query_block):
    with jax.default_matmul_precision("highest"):
        return ref_attention(
            ref_rmsnorm(h, w["norm"], eps), w, L, degrade, kv_group, query_block,
            want_heads=True)


# dynlint: disable=DYN001 -- the reference is not the serving path: no compile telemetry wanted, and one program per sublayer kind and length is the point
_SUBLAYER = jax.jit(_sublayer, static_argnums=(2, 3, 4, 5, 6))
# dynlint: disable=DYN001 -- as above
_ATTENTION_HEADS = jax.jit(_attention_heads, static_argnums=(2, 3, 4, 5, 6))


# dynlint: disable=DYN001 -- as above
@jax.jit
def _head(h, norm, head, eps):
    with jax.default_matmul_precision("highest"):
        return ref_rmsnorm(h, norm, eps) @ _f32(head)

# --- reference: end -----------------------------------------------------------


def describe_layers(config):
    """The reference's sublayer descriptions from a ``ModelConfig``."""
    out = []
    for s in config.layer_specs:
        if s.kind == "attention":
            out.append(dict(
                kind="attention", heads=s.n_heads, kv_heads=s.n_kv_heads,
                head_dim=s.head_dim, window=s.window, gate=s.gate,
                rotary=s.rope.rotary_dim, theta=float(s.rope.theta), yarn=s.rope.yarn,
                attention_factor=float(s.rope.attention_factor)))
        elif s.kind == "dense_ffn":
            out.append(dict(kind="dense_ffn"))
        elif s.kind == "experts":
            assert s.routing == "sigmoid" and s.activation == "silu_gated" and s.norm_topk
            assert s.held_ == (0, s.n_experts)
            out.append(dict(kind="experts", top_k=s.top_k, scale=float(s.scale)))
        else:
            raise ValueError(f"layer kind {s.kind!r}")
    return out
