"""A toy model module with two layer stacks, as a configuration of another
architecture would bring one (``bench/models/<model_code>.py``): a leading
``dense_blocks`` stack of ``n_dense_layers`` and a ``blocks`` stack of
``n_layers``, each layer a pre-norm SiLU MLP added to the residual stream,
the MLP of the leading stack ``d_ff_dense`` wide and that of the other
``d_ff``, then a final norm and an untied LM head.  Each layer of
``blocks`` also scales its MLP by a gate from an ``n_experts``-wide router
that it keeps in float32, as a mixture of experts keeps its router: a leaf
that states its own dtype."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.reference import mm


def stacks(m: dict) -> dict:
    return {"dense_blocks": m["n_dense_layers"], "blocks": m["n_layers"]}


def widths(m: dict) -> dict:
    return {"dense_blocks": m["d_ff_dense"], "blocks": m["d_ff"]}


def leaf_shapes(m: dict) -> dict:
    d, V = m["d_model"], m["vocab"]
    shapes = {"embed/table": ((V, d), -1), "ln_f/scale": ((d,), 0),
              "lm_head/w": ((d, V), d)}
    for stack, L in stacks(m).items():
        f = widths(m)[stack]
        shapes[f"{stack}/ln/scale"] = ((L, d), 0)
        shapes[f"{stack}/mlp/up/w"] = ((L, d, f), d)
        shapes[f"{stack}/mlp/down/w"] = ((L, f, d), f)
    shapes["blocks/router/w"] = ((m["n_layers"], d, m["n_experts"]), d,
                                 "float32")
    return shapes


def train_flops_per_token(m: dict, seq_len: int) -> float:
    d = m["d_model"]
    layers = sum(L * (2 * d * widths(m)[s] + d)
                 for s, L in stacks(m).items())
    layers += m["n_layers"] * d * m["n_experts"]
    return 6.0 * (layers + d * m["vocab"] + d)


def check_model(cfg, m: dict) -> None:
    have = {"d_model": cfg.d_model, "n_layers": cfg.n_layers,
            "d_ff": cfg.d_ff}
    diff = {k: (v, m[k]) for k, v in have.items() if v != m[k]}
    if diff:
        raise ValueError(f"the program's model differs: {diff}")


def rmsnorm(scale, x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * scale


def layer(bp, x, quant):
    h = rmsnorm(bp["ln/scale"], x)
    up = jax.nn.silu(mm("bsd,df->bsf", h, bp["mlp/up/w"], quant))
    out = mm("bsf,fd->bsd", up, bp["mlp/down/w"], quant)
    if "router/w" in bp:
        gate = jnp.tanh(mm("bsd,de->bse", h, bp["router/w"], quant))
        out = out * (1.0 + jnp.mean(gate, -1, keepdims=True))
    return x + out


def head_loss(ln_f, w, x, labels, quant, half):
    logits = mm("bsd,dv->bsv", rmsnorm(ln_f, x), w, quant)
    ce = (jax.nn.logsumexp(logits, -1)
          - jnp.take_along_axis(logits, labels[..., None], -1)[..., 0])
    if half:
        ce = ce[:, : ce.shape[1] // 2]
    return jnp.mean(ce)


def agent_grad(p: dict, tokens, labels, m: dict, quant=None, half=False):
    names = [f"{s}.{i}" for s, L in stacks(m).items() for i in range(L)]
    layers = [{k[len(n) + 1:]: v for k, v in p.items()
               if k.startswith(n + "/")} for n in names]
    xs = [p["embed/table"][tokens]]
    for bp in layers:
        xs.append(layer(bp, xs[-1], quant))
    loss, (d_lnf, d_w, dx) = jax.value_and_grad(
        head_loss, argnums=(0, 1, 2))(p["ln_f/scale"], p["lm_head/w"],
                                      xs[-1], labels, quant, half)
    grads = {"ln_f/scale": d_lnf, "lm_head/w": d_w}
    for n, bp, x in reversed(list(zip(names, layers, xs))):
        _, vjp = jax.vjp(lambda q, z: layer(q, z, quant), bp, x)
        d, dx = vjp(dx)
        grads.update({f"{n}/{k}": v for k, v in d.items()})
    grads["embed/table"] = jnp.zeros_like(p["embed/table"]).at[
        tokens.reshape(-1)].add(dx.reshape(-1, dx.shape[-1]))
    return loss, grads
