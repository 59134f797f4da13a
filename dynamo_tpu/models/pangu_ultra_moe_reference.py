"""Plain reference of the ``pangu_ultra_moe`` forward pass (tier-1's copy).

``benchmark/references/openpangu-ultra-moe-718b-ep16.py`` holds the same text
between its ``reference: begin`` / ``reference: end`` markers, so that a PR
which changes the program cannot change what the benchmark compares it with;
``tests/test_mla.py`` checks that the two agree.
"""

# --- reference: begin ---------------------------------------------------------
# The forward pass of a cut pangu_ultra_moe model in straightforward jax.numpy:
# float32, matmuls at "highest" precision, ONE sequence at a time, EXPANDED
# attention only (per-head keys and values made from the latents), no cache,
# no kernels, no batching. The experts are a loop over the experts held.
# Weights are converted to float32 one sublayer (one expert, one slice of the
# dense FFN's width) at a time, and attention runs in groups of heads and
# blocks of query positions (``head_group``, ``query_block``: the result does
# not depend on them), so the published widths fit beside the program under
# test at 16 k tokens.
#
# Departures from the published description, each because of the cut this
# configuration states (benchmark/configs/<name>.json) or of what its config
# leaves to the family's convention (the file's ``assumed``):
#   * only ``held`` = [lo, hi) of the routed experts exist; the router keeps
#     its full width and a token's weights are normalised over all its chosen
#     experts, absent ones included; what an absent expert would add is left
#     out, and that partial sum goes on to the next layer;
#   * the vocabulary is the first ``V`` rows of the embedding and of the head;
#   * one leading dense layer and the expert layers that follow it, as many
#     as ``layers`` describes; the multi-token-prediction module is not built;
#   * ``kv_b_proj`` is held as its key half ``w_kb`` and its value half
#     ``w_vb`` ([kv_rank, heads, width] each), and the rotary lanes pair lane
#     i with lane i + rope/2 (the repo's ``rotate_half`` layout): with seeded
#     random weights either is a relabelling;
#   * ``degrade``: None is the reference. "latent_int8" rounds the cached row
#     (c_kv | k_r) to 8 bits with one scale a token, "softmax_bf16" rounds
#     scores, probabilities and their sums to bfloat16, "no_rope_key" leaves
#     the shared rotary key out of the scores: each exists to show what a
#     lower precision or a dropped term reads against each limit.
import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _bf16_round(a):  # (a cast pair would be optimised away)
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def ref_rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(w)


def ref_rope(x, theta):
    """x [T, ..., D] at positions 0..T-1: lane i pairs with lane i + D/2."""
    T, D = x.shape[0], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, D // 2, dtype=jnp.float32) / (D // 2)))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (D // 2,))
    cos, sin = jnp.concatenate([jnp.cos(ang)] * 2, -1), jnp.concatenate([jnp.sin(ang)] * 2, -1)
    rot = jnp.concatenate([-x[..., D // 2:], x[..., : D // 2]], -1)
    return x * cos + rot * sin


def ref_latents(x, w, L, eps, theta, degrade=None):
    """x [T, d] -> (c_kv [T, R] normed, k_r [T, rope] rotated): the row a
    cache would hold."""
    R = L["kv_rank"]
    ckr = x @ _f32(w["w_kva"])
    c_kv, k_r = ref_rmsnorm(ckr[:, :R], w["kv_norm"], eps), ref_rope(ckr[:, R:], theta)
    if degrade == "latent_int8":
        row = jnp.concatenate([c_kv, k_r], -1)
        scale = jnp.max(jnp.abs(row), -1, keepdims=True) / 127.0
        row = jnp.round(row / scale) * scale
        c_kv, k_r = row[:, :R], row[:, R:]
    return c_kv, k_r


def ref_mla(x, w, L, eps, theta, degrade=None, head_group=None, query_block=None):
    """Causal latent attention, expanded. x [T, d] -> [T, d]."""
    T = x.shape[0]
    H, dn, dr, dv = L["heads"], L["nope"], L["rope"], L["v"]
    G, QB = head_group or H, query_block or T
    assert T % QB == 0 and H % G == 0, (T, QB, H, G)
    c_q = ref_rmsnorm(x @ _f32(w["w_qa"]), w["q_norm"], eps)
    c_kv, k_r = ref_latents(x, w, L, eps, theta, degrade)
    if degrade == "no_rope_key":
        k_r = jnp.zeros_like(k_r)
    low = _bf16_round if degrade == "softmax_bf16" else (lambda a: a)
    out = jnp.zeros((T, x.shape[1]), jnp.float32)
    for h0 in range(0, H, G):  # a group of heads at a time
        q = jnp.einsum("tr,rhk->thk", c_q, _f32(w["w_qb"][:, h0 : h0 + G]))
        q_n, q_r = q[..., :dn], ref_rope(q[..., dn:], theta)
        k_n = jnp.einsum("tr,rhk->thk", c_kv, _f32(w["w_kb"][:, h0 : h0 + G]))
        v = jnp.einsum("tr,rhk->thk", c_kv, _f32(w["w_vb"][:, h0 : h0 + G]))

        def block(r0):  # QB query positions from r0 against every key
            qn = jax.lax.dynamic_slice_in_dim(q_n, r0, QB)
            qr = jax.lax.dynamic_slice_in_dim(q_r, r0, QB)
            s = jnp.einsum("qhk,thk->hqt", qn, k_n) + jnp.einsum("qhk,tk->hqt", qr, k_r)
            s = low(s * (dn + dr) ** -0.5)
            seen = jnp.arange(T)[None, :] <= (r0 + jnp.arange(QB))[:, None]
            s = jnp.where(seen[None], s, -jnp.inf)
            p = low(jnp.exp(s - s.max(-1, keepdims=True)))
            p = low(p / low(p.sum(-1, keepdims=True)))
            return low(jnp.einsum("hqt,thk->qhk", p, v))

        o = jax.lax.map(block, jnp.arange(0, T, QB)).reshape(T, -1)
        out = out + o @ _f32(w["wo"][h0 * dv : (h0 + G) * dv])
    return out


def ref_dense_ffn(x, w, L, ffn_block=None):
    """Gated-silu FFN, a slice of its width at a time. x [T, d] -> [T, d]."""
    F = w["w_up"].shape[1]
    fb = ffn_block or F
    out = jnp.zeros_like(x)
    for f0 in range(0, F, fb):
        gate = jax.nn.silu(x @ _f32(w["w_gate"][:, f0 : f0 + fb]))
        out = out + (gate * (x @ _f32(w["w_up"][:, f0 : f0 + fb]))) @ _f32(w["w_down"][f0 : f0 + fb])
    return out


def ref_route(x, w, L):
    """(chosen expert ids [T, k], their weights [T, k], the margin [T]
    between the last chosen and the first not chosen score): sigmoid scores
    choose and weigh, no correction bias, no groups."""
    s = jax.nn.sigmoid(x @ _f32(w["router_w"]))
    top, idx = jax.lax.top_k(s, L["top_k"] + 1)
    wt = top[:, : L["top_k"]]
    wt = wt / (wt.sum(-1, keepdims=True) + 1e-20) * L["scale"]
    return idx[:, : L["top_k"]], wt, top[:, L["top_k"] - 1] - top[:, L["top_k"]]


def ref_experts(x, w, L):
    """x [T, d] -> out [T, d]: the held experts' part + the shared expert."""
    idx, wt, _ = ref_route(x, w, L)
    lo, hi = L["held"]
    ffn = lambda gate, up, down: (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)

    def expert(out, e):  # the loop over the experts held, one at a time
        e_id, gate, up, down = e
        share = jnp.where(idx == e_id, wt, 0.0).sum(-1)  # [T], 0 where not chosen
        return out + share[:, None] * ffn(gate, up, down), None

    shared = ffn(w["ws_gate"], w["ws_up"], w["ws_down"])
    out, _ = jax.lax.scan(
        expert, shared, (jnp.arange(lo, hi), w["we_gate"], w["we_up"], w["we_down"]))
    return out


def reference_forward(weights, layers, tokens, eps, theta, positions=None, degrade=None,
                      head_group=None, query_block=None, ffn_block=None):
    """tokens [T] -> {"logits" [n, V] at ``positions`` (default: all),
    "hidden": the input of every sublayer at ``positions`` [n, d]}."""
    keep = jnp.arange(len(tokens)) if positions is None else jnp.asarray(positions)
    with jax.default_matmul_precision("highest"):
        h = _f32(weights["embed"][jnp.asarray(tokens)])
        hidden = []
        for w, L in zip(weights["layers"], layers):
            hidden.append(h[keep])
            h = _SUBLAYER(h, w, _Static(L), eps, theta, degrade, head_group, query_block, ffn_block)
        return {"logits": _head(h[keep], weights["final_norm"], weights["lm_head"], eps),
                "hidden": hidden}


class _Static(dict):
    """A sublayer description as a static (hashable) argument: one compiled
    function per sublayer kind and sequence length, not one per call."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _sublayer(h, w, L, eps, theta, degrade, head_group, query_block, ffn_block):
    """h <- h + N_post(F(N_pre(h))): sandwich norms where ``post_norm``."""
    with jax.default_matmul_precision("highest"):
        x = ref_rmsnorm(h, w["norm"], eps)
        if L["kind"] == "mla":
            out = ref_mla(x, w, L, eps, theta, degrade, head_group, query_block)
        elif L["kind"] == "dense_ffn":
            out = ref_dense_ffn(x, w, L, ffn_block)
        else:
            out = ref_experts(x, w, L)
        if L["post_norm"]:
            out = ref_rmsnorm(out, w["post_norm"], eps)
        return h + out


# dynlint: disable=DYN001 -- the reference is not the serving path: no compile telemetry wanted, and one program per sublayer kind and length is the point
_SUBLAYER = jax.jit(_sublayer, static_argnums=(2, 3, 4, 5, 6, 7, 8))


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
        if s.kind == "mla":
            out.append(dict(kind="mla", heads=s.n_heads, q_rank=s.q_rank, kv_rank=s.kv_rank,
                            nope=s.nope_dim, rope=s.rope_dim, v=s.v_dim, post_norm=s.post_norm))
        elif s.kind == "dense_ffn":
            out.append(dict(kind="dense_ffn", post_norm=s.post_norm))
        elif s.kind == "experts":
            assert s.routing == "sigmoid" and s.activation == "silu_gated" and s.norm_topk
            out.append(dict(kind="experts", top_k=s.top_k, scale=float(s.scale),
                            held=tuple(s.held_), post_norm=s.post_norm))
        else:
            raise ValueError(f"layer kind {s.kind!r}")
    return out
