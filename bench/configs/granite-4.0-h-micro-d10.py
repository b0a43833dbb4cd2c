"""granite-4.0-h-micro at 10 of its 40 layers: the program's parts built
from the configuration, the weights the benchmark makes, the plain float32
reference of its forward pass and RL loss, and the operations and bytes
its steps and its SSD kernel need, from its shapes.

The reference follows the published Granite 4.0-H decoder (model type
granitemoehybrid; its config.json and modelling code): the embedding times
``embedding_multiplier``; in every layer x += r * mixer(RMSNorm(x)) and
x += r * MLP(RMSNorm(x)), r the ``residual_multiplier``, the mixer
Mamba-2 or attention as ``layer_types`` says; logits = RMSNorm(x) E^T /
``logits_scaling``, the embedding tied.  Attention: grouped-query, no
positional encoding, no bias, softmax scale ``attention_multiplier``.
Mamba-2: in_proj -> (z, xBC, dt); a causal depthwise conv with bias and
silu over xBC -> (x, B, C); dt = softplus(dt + dt_bias), A = -exp(A_log);
S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + D x_t, run
here as the sequential recurrence over time; RMSNorm(y * silu(z)) over
the inner width; out_proj.  Its one addition is the RL value head
(d_model -> 1 on the final normed state), which the policy agent adds to
the model.  It imports nothing of the program: only ``program_parts``
does, inside its body.
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
    Hs, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    N = cfg["mamba_d_state"]
    return {
        "d": d, "H": H, "K": cfg["num_key_value_heads"], "h": d // H,
        "F": cfg["shared_intermediate_size"], "V": cfg["vocab_size"],
        "L": cfg["num_hidden_layers"], "types": list(cfg["layer_types"]),
        "Hs": Hs, "P": P, "N": N, "di": Hs * P, "W": cfg["mamba_d_conv"],
        "conv": Hs * P + 2 * cfg["mamba_n_groups"] * N,
    }


# ---------------------------------------------------------------- program


def program_parts(cfg: dict, traffic: dict):
    """The system under test for this configuration and traffic: the
    LM policy agent (the Granite stack with a value head) and the
    token-copy device env -> (``Sebulba`` keyword arguments, loss
    settings for ``SebulbaConfig``)."""
    from repro.agents.lm_policy import LMPolicyAgent
    from repro.configs.base import ArchConfig
    from repro.envs import TokenEnv
    from repro.launch.steps import TrainHParams

    s = dims(cfg)
    if cfg["mamba_n_groups"] != 1 or s["di"] % cfg["hidden_size"]:
        raise ValueError("the program's Mamba-2 mixer has one group and an "
                         "inner width that is a multiple of hidden_size")
    if cfg["position_embedding_type"] != "nope" or cfg["attention_bias"]:
        raise ValueError("granite-4.0-h attention has no positions, no bias")
    arch = ArchConfig(
        name=cfg["name"], family="dense", source=cfg["source"],
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=s["h"],
        d_ff=cfg["shared_intermediate_size"], vocab_size=cfg["vocab_size"],
        layer_types=tuple(cfg["layer_types"]),
        attention_multiplier=cfg["attention_multiplier"], pos_embed="none",
        ssm_state=s["N"], ssm_expand=s["di"] // cfg["hidden_size"],
        ssm_head_dim=s["P"], ssm_chunk=cfg["mamba_chunk_size"],
        conv_width=s["W"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=float(cfg["logits_scaling"]),
        rms_norm_eps=cfg["rms_norm_eps"],
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
    (one subtree per layer); matrices and the conv in bfloat16, norm
    scales, the conv bias and the SSM's per-head scalars in float32."""
    s = dims(cfg)
    d, H, K, h, F, V = s["d"], s["H"], s["K"], s["h"], s["F"], s["V"]
    Hs, N, di, W, C = s["Hs"], s["N"], s["di"], s["W"], s["conv"]
    bf, f = jnp.bfloat16, F32
    out = {("embedding", "table"): ((V, d), bf)}
    for i, kind in enumerate(s["types"]):
        n = f"layer_{i}"
        if kind == "mamba":
            out.update({
                (n, "mixer", "norm", "scale"): ((d,), f),
                (n, "mixer", "in_proj"): ((d, 2 * di + 2 * N + Hs), bf),
                (n, "mixer", "conv_w"): ((W, C), bf),
                (n, "mixer", "conv_b"): ((C,), f),
                (n, "mixer", "A_log"): ((Hs,), f),
                (n, "mixer", "dt_bias"): ((Hs,), f),
                (n, "mixer", "D"): ((Hs,), f),
                (n, "mixer", "out_norm", "scale"): ((di,), f),
                (n, "mixer", "out_proj"): ((di, d), bf),
            })
        else:
            out.update({
                (n, "attn_norm", "scale"): ((d,), f),
                (n, "attn", "wq"): ((d, H, h), bf),
                (n, "attn", "wk"): ((d, K, h), bf),
                (n, "attn", "wv"): ((d, K, h), bf),
                (n, "attn", "wo"): ((H, h, d), bf),
            })
        out.update({
            (n, "ffn_norm", "scale"): ((d,), f),
            (n, "mlp", "w_gate"): ((d, F), bf),
            (n, "mlp", "w_up"): ((d, F), bf),
            (n, "mlp", "w_down"): ((F, d), bf),
        })
    out[("final_norm", "scale")] = ((d,), f)
    out[("value_head", "w")] = ((d, 1), bf)
    return out


def _inv_softplus(y):
    return y + jnp.log(-jnp.expm1(-y))


def make_params(cfg: dict, key) -> dict:
    """Seeded weights, traced inside one jit by the caller: matrices
    normal with std 1/sqrt(fan_in) (the embedding 0.02, the conv
    1/sqrt(width), its bias 0.02), norm scales about 1; A_log, dt_bias and
    D as Mamba-2 initialises them (the configuration's ``assumed``)."""
    tree: dict = {}
    for i, (path, (shape, dtype)) in enumerate(sorted(param_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        leaf, name = path[-2], path[-1]
        if name == "scale":
            x = 1.0 + 0.1 * jax.random.normal(k, shape, F32)
        elif name == "A_log":
            x = jnp.log(jax.random.uniform(k, shape, F32, 1.0, 16.0))
        elif name == "dt_bias":
            u = jax.random.uniform(k, shape, F32)
            x = _inv_softplus(jnp.exp(math.log(1e-3) + u * math.log(100.0)))
        elif name == "D":
            x = jnp.ones(shape, F32)
        elif name == "conv_b" or leaf == "embedding":
            x = 0.02 * jax.random.normal(k, shape, F32)
        elif name == "conv_w":
            x = jax.random.normal(k, shape, F32) / math.sqrt(shape[0])
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


def _mamba(m, x, s, eps, num):
    """The Mamba-2 mixer on (B, S, d), the SSM as its sequential
    recurrence over time from the zero state."""
    B, S, _ = x.shape
    Hs, P, N, di, W = s["Hs"], s["P"], s["N"], s["di"], s["W"]
    y = _rms(x, m["norm"]["scale"], eps)
    proj = num.einsum("bsd,de->bse", y, m["in_proj"])
    z, xbc, dt = proj[..., :di], proj[..., di:-Hs], proj[..., -Hs:]
    pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + S] * m["conv_w"][i] for i in range(W))
    xbc = jax.nn.silu(conv + m["conv_b"])
    xs = xbc[..., :di].reshape(B, S, Hs, P)
    Bm, Cm = xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(dt + m["dt_bias"])  # (B, S, Hs)
    A = -jnp.exp(m["A_log"])

    def step(state, inp):
        xt, dtt, bt, ct = inp
        state = (jnp.exp(dtt * A)[..., None, None] * state
                 + num.einsum("bhp,bn->bhpn", xt * dtt[..., None], bt))
        return state, num.einsum("bhpn,bn->bhp", state, ct)

    # each step keeps only its incoming state for the backward pass (17
    # states of 134 MB a layer at the cell's size), not its products
    state0 = jnp.zeros((B, Hs, P, N), F32)
    _, ys = jax.lax.scan(jax.checkpoint(step), state0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (xs, dt, Bm, Cm)))
    ys = jnp.moveaxis(ys, 0, 1) + m["D"][:, None] * xs
    ys = ys.reshape(B, S, di) * jax.nn.silu(z)
    return num.einsum("bse,ed->bsd", _rms(ys, m["out_norm"]["scale"], eps),
                      m["out_proj"])


def _attention(lp, x, s, scale, eps, num):
    """Causal grouped-query attention on (B, S, d): no positions, no
    bias, softmax scale ``scale``."""
    B, S, _ = x.shape
    K, G = s["K"], s["H"] // s["K"]
    a = lp["attn"]
    y = _rms(x, lp["attn_norm"]["scale"], eps)
    q = num.einsum("bsd,dnh->bsnh", y, a["wq"]).reshape(B, S, K, G, s["h"])
    k = num.einsum("bsd,dnh->bsnh", y, a["wk"])
    v = num.einsum("bsd,dnh->bsnh", y, a["wv"])
    att = num.einsum("bskgh,btkh->bkgst", q, k) * scale
    causal = jnp.tril(jnp.ones((S, S), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    o = num.einsum("bkgst,btkh->bskgh", att, v).reshape(B, S, s["H"], s["h"])
    return num.einsum("bsnh,nhd->bsd", o, a["wo"])


def forward(params, tokens, cfg: dict, num: ref.Numerics = ref.HIGHEST):
    """tokens (B, S) -> (logits (B, S, V), values (B, S)), float32;
    ``params`` in the program's layout."""
    s = dims(cfg)
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    p = jax.tree.map(lambda a: a.astype(F32), params)
    x = jnp.take(p["embedding"]["table"], tokens, axis=0)
    x = x * cfg["embedding_multiplier"]

    def layer(kind, lp, x):
        if kind == "mamba":
            x = x + r * _mamba(lp["mixer"], x, s, eps, num)
        else:
            x = x + r * _attention(lp, x, s, cfg["attention_multiplier"],
                                   eps, num)
        y = _rms(x, lp["ffn_norm"]["scale"], eps)
        m = lp["mlp"]
        g = num.einsum("bsd,df->bsf", y, m["w_gate"])
        u = num.einsum("bsd,df->bsf", y, m["w_up"])
        return x + r * num.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                                  m["w_down"])

    # one layer's activations at a time are kept for the backward pass,
    # so that the reference fits beside its optimizer state
    for i, kind in enumerate(s["types"]):
        x = jax.checkpoint(layer, static_argnums=0)(kind, p[f"layer_{i}"], x)
    x = _rms(x, p["final_norm"]["scale"], eps)
    logits = num.einsum("bsd,vd->bsv", x, p["embedding"]["table"])
    logits = logits / cfg["logits_scaling"]
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
    """Weights that take part in a matrix product for each token: the
    mixers' projections, the MLPs, the tied table as the output
    projection, the value head.  The embedding lookup is a gather, and
    the conv a per-channel product (``token_flops`` counts it)."""
    s = dims(cfg)
    d, H, K, h, F = s["d"], s["H"], s["K"], s["h"], s["F"]
    mamba = d * (2 * s["di"] + 2 * s["N"] + s["Hs"]) + s["di"] * d
    attn = d * H * h * 2 + d * K * h * 2
    n_attn = s["types"].count("attention")
    return (n_attn * attn + (s["L"] - n_attn) * mamba + s["L"] * 3 * d * F
            + s["V"] * d + d)


def token_flops(cfg: dict, context: float) -> float:
    """Forward operations for one token that attends to ``context``
    positions: 2 per weight, QK^T and PV in each attention layer, and in
    each Mamba-2 layer its conv (2 per tap and channel) and its
    recurrence (2 per state element to update the state, 2 to read it
    out)."""
    s = dims(cfg)
    n_attn = s["types"].count("attention")
    ssm = 2 * s["W"] * s["conv"] + 4 * s["Hs"] * s["P"] * s["N"]
    return (2.0 * matmul_params(cfg) + 4.0 * n_attn * s["H"] * s["h"] * context
            + (s["L"] - n_attn) * ssm)


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
    once; each row's Mamba-2 state (float32) and conv window (bfloat16)
    read and written in every Mamba-2 layer; the attention layers' live
    keys and values read and one position of them written; averaged over
    the episode's positions."""
    s = dims(cfg)
    B, T = traffic["actor_batch_size"], traffic["trajectory_length"]
    mean_ctx = (T + 1) / 2.0
    n_attn = s["types"].count("attention")
    kv_row = 2 * n_attn * s["K"] * s["h"] * 2  # k and v, bf16, per position
    ssm_row = (s["L"] - n_attn) * (
        s["Hs"] * s["P"] * s["N"] * 4 + (s["W"] - 1) * s["conv"] * 2)
    nbytes = param_bytes(cfg) + B * (kv_row * (mean_ctx + 1) + 2 * ssm_row)
    return B * token_flops(cfg, mean_ctx), float(nbytes)


def ssd_scan_cost(cfg: dict, traffic: dict) -> tuple[float, float]:
    """(operations, bytes) one call of the SSD forward kernel needs at
    least in the learner's update: the chunked SSD over the T + 1 tokens
    of the traffic's rows (chunks of ``mamba_chunk_size``; the kernel's
    padding of the sequence not counted), per row and head a chunk's C B^T
    (2 Q^2 N), its masked product with x (2 Q^2 P), and the read-out of
    and write to the state (2 Q P N each); x (bfloat16), dt (float32), B
    and C (bfloat16) read once, y (bfloat16) and the final state (float32)
    written."""
    s = dims(cfg)
    B, T = traffic["actor_batch_size"], traffic["trajectory_length"] + 1
    Hs, P, N = s["Hs"], s["P"], s["N"]
    Q = min(cfg["mamba_chunk_size"], T)
    chunks, last = divmod(T, Q)
    quad = chunks * Q * Q + last * last
    flops = B * Hs * (2.0 * quad * (N + P) + 4.0 * T * P * N)
    nbytes = (2 * B * T * Hs * P * 2 + B * T * Hs * 4 + 2 * B * T * N * 2
              + B * Hs * P * N * 4 + Hs * 4)
    return flops, float(nbytes)
