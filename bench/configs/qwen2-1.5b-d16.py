"""qwen2-1.5b at 16 of its 28 layers: the program's parts built from the
configuration, the weights the benchmark makes, the plain float32
reference of its forward pass and RL loss, and the operations and bytes
its steps need, from its shapes.

The reference follows the published Qwen2 decoder (arXiv:2407.10671):
RMSNorm, grouped-query attention with QKV bias and rotate-half RoPE,
SwiGLU MLP, tied input and output embeddings.  Its one addition is the
RL value head (d_model -> 1 on the final normed state), which the policy
agent adds to the model.  It imports nothing of the program: only
``program_parts`` does, inside its body.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import reference as ref

F32 = jnp.float32


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return {
        "d": d, "H": H, "K": cfg["num_key_value_heads"],
        "h": cfg.get("head_dim", d // H), "F": cfg["intermediate_size"],
        "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
    }


# ---------------------------------------------------------------- program


def program_parts(cfg: dict, traffic: dict):
    """The system under test for this configuration and traffic: the
    LM policy agent (a dense Qwen2 decoder with a value head) and the
    token-copy device env -> (``Sebulba`` keyword arguments, loss
    settings for ``SebulbaConfig``)."""
    from repro.agents.lm_policy import LMPolicyAgent
    from repro.configs.base import ArchConfig
    from repro.envs import TokenEnv
    from repro.launch.steps import TrainHParams

    arch = ArchConfig(
        name=cfg["name"], family="dense", source=cfg["source"],
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], qkv_bias=cfg["qkv_bias"],
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
    )
    env = TokenEnv(vocab_size=arch.vocab_size,
                   prompt_len=traffic["prompt_len"],
                   data_vocab=traffic["data_vocab"])
    if traffic["trajectory_length"] != env.episode_len:
        raise ValueError("LM cells train on whole episodes: "
                         "trajectory_length must be 2 * prompt_len")
    hp = cfg["loss"]
    agent = LMPolicyAgent(arch, max_seq=env.episode_len, hparams=TrainHParams(
        rl_weight=hp["rl_weight"], entropy_cost=hp["entropy_cost"],
        value_cost=hp["value_cost"], aux_weight=hp["aux_weight"],
    ))
    return {"agent": agent, "device_env": env}, {}


# ---------------------------------------------------------------- weights


def param_shapes(cfg: dict) -> dict:
    """{path: (shape, dtype)} in the layout the policy agent's model keeps
    (one subtree per layer); matrices in bfloat16, norm scales and biases
    in float32."""
    s = dims(cfg)
    d, H, K, h, F, V = s["d"], s["H"], s["K"], s["h"], s["F"], s["V"]
    bf, f = jnp.bfloat16, F32
    out = {("embedding", "table"): ((V, d), bf)}
    for i in range(s["L"]):
        n = f"layer_{i}"
        out.update({
            (n, "attn_norm", "scale"): ((d,), f),
            (n, "attn", "wq"): ((d, H, h), bf),
            (n, "attn", "wk"): ((d, K, h), bf),
            (n, "attn", "wv"): ((d, K, h), bf),
            (n, "attn", "wo"): ((H, h, d), bf),
            (n, "attn", "bq"): ((H, h), f),
            (n, "attn", "bk"): ((K, h), f),
            (n, "attn", "bv"): ((K, h), f),
            (n, "ffn_norm", "scale"): ((d,), f),
            (n, "mlp", "w_gate"): ((d, F), bf),
            (n, "mlp", "w_up"): ((d, F), bf),
            (n, "mlp", "w_down"): ((F, d), bf),
        })
    out[("final_norm", "scale")] = ((d,), f)
    out[("value_head", "w")] = ((d, 1), bf)
    return out


def make_params(cfg: dict, key) -> dict:
    """Seeded weights, traced inside one jit by the caller: matrices
    normal with std 1/sqrt(fan_in) (the embedding 0.02), norm scales
    about 1, biases about 0."""
    tree: dict = {}
    for i, (path, (shape, dtype)) in enumerate(sorted(param_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        leaf, name = path[-2], path[-1]
        if name == "scale":
            x = 1.0 + 0.1 * jax.random.normal(k, shape, F32)
        elif name.startswith("b"):
            x = 0.02 * jax.random.normal(k, shape, F32)
        elif leaf == "embedding":
            x = 0.02 * jax.random.normal(k, shape, F32)
        else:
            fan_in = shape[0] * shape[1] if name == "wo" else shape[0]
            x = jax.random.normal(k, shape, F32) / math.sqrt(fan_in)
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[name] = x.astype(dtype)
    return tree


# -------------------------------------------------------------- reference


def _rms(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotate-half RoPE over positions 0..T-1; x (B, T, heads, h)."""
    h = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, h, 2, dtype=F32) / h)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv  # (T, h/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : h // 2], x[..., h // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def to_reference(params: dict) -> dict:
    """The reference's own layout: the layers stacked on a leading axis,
    so that its forward pass is one scanned layer."""
    n = sum(k.startswith("layer_") for k in params)
    out = {k: v for k, v in params.items() if not k.startswith("layer_")}
    out["layers"] = jax.tree.map(
        lambda *xs: jnp.stack(xs), *[params[f"layer_{i}"] for i in range(n)])
    return out


def leaf_sumsq(tree: dict) -> dict:
    """Sums of squares keyed by the program's leaf paths, from a tree in
    the reference's layout (one entry per layer of a stacked leaf)."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = x.astype(F32)
        if path[0].key == "layers":
            per = jnp.sum(x * x, axis=tuple(range(1, x.ndim)))
            for i in range(x.shape[0]):
                key = (jax.tree_util.DictKey(f"layer_{i}"),) + tuple(path[1:])
                out[jax.tree_util.keystr(key)] = per[i]
        else:
            out[jax.tree_util.keystr(path)] = jnp.sum(x * x)
    return out


def forward(params, tokens, cfg: dict, num: ref.Numerics = ref.HIGHEST):
    """tokens (B, S) -> (logits (B, S, V), values (B, S)), float32;
    ``params`` in the reference's layout."""
    s = dims(cfg)
    K, G = s["K"], s["H"] // s["K"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    p = jax.tree.map(lambda a: a.astype(F32), params)
    B, S = tokens.shape
    x = jnp.take(p["embedding"]["table"], tokens, axis=0)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, lp):
        a = lp["attn"]
        y = _rms(x, lp["attn_norm"]["scale"], eps)
        q = _rope(num.einsum("bsd,dnh->bsnh", y, a["wq"]) + a["bq"], theta)
        k = _rope(num.einsum("bsd,dnh->bsnh", y, a["wk"]) + a["bk"], theta)
        v = num.einsum("bsd,dnh->bsnh", y, a["wv"]) + a["bv"]
        q = q.reshape(B, S, K, G, s["h"]) * s["h"] ** -0.5
        att = num.einsum("bskgh,btkh->bkgst", q, k)
        att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
        o = num.einsum("bkgst,btkh->bskgh", att, v).reshape(B, S, s["H"], s["h"])
        x = x + num.einsum("bsnh,nhd->bsd", o, a["wo"])
        y = _rms(x, lp["ffn_norm"]["scale"], eps)
        m = lp["mlp"]
        g = num.einsum("bsd,df->bsf", y, m["w_gate"])
        u = num.einsum("bsd,df->bsf", y, m["w_up"])
        return x + num.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, m["w_down"]), None

    # one layer's activations at a time are kept for the backward pass,
    # so that the reference fits beside its optimizer state
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, p["layers"])
    x = _rms(x, p["final_norm"]["scale"], eps)
    logits = num.einsum("bsd,vd->bsv", x, p["embedding"]["table"])
    values = num.einsum("bsd,dk->bsk", x, p["value_head"]["w"])[..., 0]
    return logits, values


def loss(params, traj, cfg: dict, num: ref.Numerics = ref.HIGHEST):
    """The policy agent's objective on a block of rows: next-token
    cross-entropy over the episode teacher-forced, plus rl_weight times
    the V-trace actor-critic loss.  -> (loss, log pi(a_t) (B, T))."""
    hp = cfg["loss"]
    T = traj["actions"].shape[1]
    tokens = jnp.concatenate(
        [traj["obs"], traj["bootstrap_obs"][:, None]], axis=1
    ).astype(jnp.int32)
    logits, values = forward(params, tokens, cfg, num)
    lt = logits[:, :T]
    lse = jax.nn.logsumexp(lt, axis=-1)
    tgt = jnp.take_along_axis(lt, tokens[:, 1:, None], axis=-1)[..., 0]
    ce = jnp.mean(lse - tgt)
    rl, logp = ref.impala_terms(
        lt, values[:, :T], values[:, T], traj,
        entropy_cost=hp["entropy_cost"], value_cost=hp["value_cost"],
    )
    return ce + hp["rl_weight"] * rl, logp


# ---------------------------------------------------- operations and bytes


def param_count(cfg: dict) -> int:
    return sum(math.prod(s) for s, _ in param_shapes(cfg).values())


def param_bytes(cfg: dict) -> int:
    return sum(
        math.prod(s) * jnp.dtype(t).itemsize
        for s, t in param_shapes(cfg).values()
    )


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product for each token: every
    layer matrix, the tied table as the output projection, the value
    head.  The embedding lookup is a gather, not a product."""
    s = dims(cfg)
    d, H, K, h, F = s["d"], s["H"], s["K"], s["h"], s["F"]
    layer = d * H * h * 2 + d * K * h * 2 + 3 * d * F
    return s["L"] * layer + s["V"] * d + d


def token_flops(cfg: dict, context: float) -> float:
    """Forward operations for one token that attends to ``context``
    positions: 2 per weight, plus QK^T and PV."""
    s = dims(cfg)
    return 2.0 * matmul_params(cfg) + 4.0 * s["L"] * s["H"] * s["h"] * context


def flops_per_frame(cfg: dict, traffic: dict) -> float:
    """Model operations per trained frame (one token of one row): the
    actor's decode step, plus forward and backward (3x forward) over the
    learner's teacher-forced T + 1 tokens, shared by the T frames of a
    row.  Causal attention sees (t + 1) positions at position t;
    recomputation is not counted."""
    T = traffic["trajectory_length"]
    mean_ctx = (T + 1) / 2.0  # decode: positions 1..T
    learn_ctx = (T + 2) / 2.0  # forward over T + 1 tokens
    return token_flops(cfg, mean_ctx) + 3.0 * token_flops(cfg, learn_ctx) * (T + 1) / T


def act_step_cost(cfg: dict, traffic: dict) -> tuple[float, float]:
    """(operations, bytes) one act step needs at least: every weight read
    once, the live keys and values read, one position of them written;
    averaged over the episode's positions."""
    s = dims(cfg)
    B, T = traffic["actor_batch_size"], traffic["trajectory_length"]
    mean_ctx = (T + 1) / 2.0
    kv_row = 2 * s["L"] * s["K"] * s["h"] * 2  # k and v, bf16, per position
    nbytes = param_bytes(cfg) + B * kv_row * (mean_ctx + 1)
    return B * token_flops(cfg, mean_ctx), float(nbytes)
