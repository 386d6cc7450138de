"""Plain float32 reference of the `glm4_moe_lite` block family
(GLM-4.7-Flash): latent attention (MLA), a SiLU-gated dense layer, the
sigmoid top-k expert layer with a selection-only bias and a shared
expert, over a HELD range of the experts, and the depth-1 multi-token-
prediction block.  Whole batch at once, every head's [T, T] scores and
every expert over every token: no kernel, no blocking, nothing imported
from `mxtpu`.  `benchmark/onchip/reference/glm_4_7_flash.py` is the
benchmark's copy of the same equations, computed in blocks so that it
fits on a chip at the published widths; `tests/test_glm_moe_lite.py`
holds the two to the same loss and gradients.

The config is a dict under the published `config.json`'s keys plus
`experts_held`, `expert_first`, `mtp_loss_weight`.  Parameters are a
flat dict under the program's names: a layer leaf is stacked over its
segment's layers (`dense.<leaf>`, `<leaf>`, `mtp.<leaf>`).
"""
import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HI)


def rms(cfg, x, scale):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True)
                        + cfg["rms_norm_eps"]) * scale


def rotary(cfg, x):
    """x [..., T, d] at positions 0..T-1; dim i pairs with i + d/2."""
    T, d = x.shape[-2:]
    inv = cfg["rope_theta"] ** (-jnp.arange(d // 2) / (d // 2))
    ang = jnp.arange(T)[:, None] * inv[None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def mla(cfg, x, w):
    B, T, _ = x.shape
    H = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, kvl = cfg["v_head_dim"], cfg["kv_lora_rank"]
    c_q = rms(cfg, mm("bte,ef->btf", x, w["wq_a"]), w["q_norm"])
    q = mm("bte,ef->btf", c_q, w["wq_b"]).reshape(B, T, H, dn + dr)
    q = q.transpose(0, 2, 1, 3)
    q = jnp.concatenate([q[..., :dn], rotary(cfg, q[..., dn:])], -1)
    ckv = mm("bte,ef->btf", x, w["wkv_a"])
    c_kv = rms(cfg, ckv[..., :kvl], w["kv_norm"])
    k_pe = rotary(cfg, ckv[..., kvl:])[:, None]            # one key
    kv = mm("bte,ef->btf", c_kv, w["wkv_b"]).reshape(B, T, H, dn + dv)
    kv = kv.transpose(0, 2, 1, 3)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (B, H, T, dr))], -1)
    s = mm("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(float(dn + dr))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = mm("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), kv[..., dn:])
    return mm("bte,ef->btf", o.transpose(0, 2, 1, 3).reshape(B, T, H * dv),
              w["wo"])


def gated(x, wg, wu, wd):
    return mm("...f,fe->...e",
              jax.nn.silu(mm("...e,ef->...f", x, wg))
              * mm("...e,ef->...f", x, wu), wd)


def route(cfg, z, router, bias):
    """(selected experts [n, k], weights [n, k]) of tokens z [n, E]."""
    s = jax.nn.sigmoid(mm("ne,ex->nx", z, router))
    _, idx = lax.top_k(s + bias, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, 1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * cfg["routed_scaling_factor"]


def routed_part(cfg, z, w, first, held):
    """Sum over the experts first..first+held-1 (whose matrices `w`
    holds on the leading axis of we_*) of weight * Expert(z)."""
    idx, wt = route(cfg, z, w["router"], w["router_bias"])
    out = jnp.zeros_like(z)
    for e in range(held):
        we = jnp.where(idx == first + e, wt, 0.0).sum(-1)
        out = out + we[:, None] * gated(z, w["we_g"][e], w["we_u"][e],
                                        w["we_d"][e])
    return out


def shared_part(z, w):
    return gated(z, w["ws_g"], w["ws_u"], w["ws_d"])


def layer(cfg, kind, x, w):
    h = x + mla(cfg, rms(cfg, x, w["ln1"]), w)
    z = rms(cfg, h, w["ln2"])
    if kind == "dense":
        return h + gated(z, w["wg"], w["wu"], w["wd"])
    flat = z.reshape(-1, z.shape[-1])
    f = shared_part(flat, w) + routed_part(
        cfg, flat, w, cfg.get("expert_first", 0), cfg["experts_held"])
    return h + f.reshape(z.shape)


def segments(cfg):
    dense = cfg["first_k_dense_replace"]
    out = [("dense.", "dense", dense),
           ("", "moe", cfg["num_hidden_layers"] - dense)]
    if cfg["num_nextn_predict_layers"]:
        out.append(("mtp.", "moe", 1))
    return out


ATTN = ("ln1", "ln2", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
        "wkv_b", "wo")
LEAVES = {"dense": ATTN + ("wg", "wu", "wd"),
          "moe": ATTN + ("router", "router_bias", "we_g", "we_u", "we_d",
                         "ws_g", "ws_u", "ws_d")}


def run_segment(cfg, p, prefix, kind, n, x):
    for i in range(n):
        x = layer(cfg, kind, x, {k: p[prefix + k][i] for k in LEAVES[kind]})
    return x


def xent(lg, labels):
    return (jax.nn.logsumexp(lg, -1)
            - jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]).mean()


def loss(cfg, p, tokens, labels):
    """Mean cross-entropy of the main head, plus `mtp_loss_weight` times
    the MTP head's over the positions that have a token after next."""
    x = p["embed"][tokens]
    for prefix, kind, n in segments(cfg):
        if prefix != "mtp.":
            x = run_segment(cfg, p, prefix, kind, n, x)
    out = xent(mm("bte,ev->btv", rms(cfg, x, p["ln_f"]), p["unembed"]),
               labels)
    if cfg["num_nextn_predict_layers"]:
        u = jnp.concatenate(
            [rms(cfg, p["embed"][tokens[:, 1:]], p["mtp.ln_e"]),
             rms(cfg, x[:, :-1], p["mtp.ln_h"])], -1)
        u = run_segment(cfg, p, "mtp.", "moe", 1,
                        mm("bte,ef->btf", u, p["mtp.eh"]))
        out = out + cfg["mtp_loss_weight"] * xent(
            mm("bte,ev->btv", rms(cfg, u, p["mtp.ln_f"]), p["unembed"]),
            labels[:, 1:])
    return out
