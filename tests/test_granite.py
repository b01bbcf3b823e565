"""granite-4.0-h-small as a federated LoRA client workload, at a tiny size on
the CPU, against the plain float32 reference (``models/granite_reference``)
and, where ``torch`` and ``transformers`` are installed, against the
upstream ``GraniteMoeHybridForCausalLM``.

Tolerances: the program and the references all compute in float32 here, so
what separates them is the order of summation (the SSD's chunks against its
quadratic form, blocked online-softmax attention against the full score
matrix, the dense expert share against a loop over experts, merged against
unmerged adapters).  Each bound is a few hundred float32 ulps of the
compared quantity's scale; a path that computed in bf16 would miss them by
two orders of magnitude.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import markov_sequences
from repro.fed.api import run
from repro.fed.engine import _BATCH_STREAM
from repro.fed.server import ServerConfig
from repro.fed.simulator import SimConfig
from repro.fed.workload import (
    _adapter_sites,
    _attached_params,
    _init_fn,
    _lora_loss_fn,
    _merged_params,
    get_workload,
)
from repro.models import build_model
from repro.models import granite_reference as ref
from repro.models.layers import init_mlp
from repro.models.moe import apply_moe_share, init_moe_share
from repro.models.pattern import runs
from repro.utils import spans

TINY = get_config("granite-4.0-h-small").with_(
    num_layers=3, layer_types=("mamba", "attention", "mamba"),
    d_model=64, num_heads=4, num_kv_heads=2, head_dim=16, d_ff=32,
    shared_d_ff=48, num_experts=8, top_k=3, experts_held=(2, 5),
    ssm_state=16, ssm_head_dim=16, ssm_chunk=8, vocab_size=97,
    attention_multiplier=1 / 16, param_dtype="float32", compute_dtype="float32",
    block_q=8, block_k=8,
)
RANK, ALPHA = 4, 8.0
WL = get_workload("lora", model_cfg=TINY, rank=RANK, alpha=ALPHA)

# the reference, jitted once per configuration (it is plain jnp)
ref_logits = jax.jit(ref.logits, static_argnums=(1,))
ref_loss = jax.jit(ref.loss, static_argnums=(1,))
ref_grads = jax.jit(ref.adapter_grads, static_argnums=(1,))


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _params_and_adapters(seed=0):
    """Base and adapters with B drawn non-zero, so the adapter path counts."""
    p = _init_fn(WL)(jax.random.PRNGKey(seed))
    leaves, tdef = jax.tree_util.tree_flatten(p["adapters"])
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    leaves = [l + 0.05 * jax.random.normal(k, l.shape) for l, k in zip(leaves, keys)]
    return WL, p["base"], jax.tree_util.tree_unflatten(tdef, leaves)


def _tokens(seed=3, b=2, length=21):
    seqs = markov_sequences(seed, TINY.vocab_size, b, length + 1)
    return jnp.asarray(seqs[:, :-1]), jnp.asarray(seqs[:, 1:])


def test_full_config_is_published():
    cfg = get_config("granite-4.0-h-small")
    assert [i for i, k in enumerate(cfg.layer_types) if k == "attention"] == [5, 15, 25, 35]
    assert (cfg.d_model, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state) == (4096, 128, 64, 128)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.hd) == (32, 8, 128)
    assert (cfg.num_experts, cfg.top_k, cfg.d_ff, cfg.shared_d_ff) == (72, 10, 768, 1536)
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    total = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes))
    assert abs(total / 32.2e9 - 1) < 0.01, total  # 32B-A9B


def test_runs_group_consecutive_kinds():
    assert runs(get_config("granite-4.0-h-small").layer_types[:10]) == [
        ("mamba", 0, 5), ("attention", 0, 1), ("mamba", 5, 4)]


def test_forward_loss_and_adapter_grads_match_reference():
    wl, base, adapters = _params_and_adapters()
    tokens, labels = _tokens()
    model = build_model(TINY)
    eff = _attached_params(base, adapters, wl.scaling)
    got = jax.jit(model.forward)(eff, {"tokens": tokens})
    want = ref_logits(base, TINY, tokens, adapters, wl.scaling)
    assert _rel(got, want) < 2e-5
    loss = _lora_loss_fn(TINY, wl.targets, wl.scaling)
    (l_got, stats), g_got = jax.jit(jax.value_and_grad(
        lambda a: loss(base, a, {"x": tokens, "y": labels}), has_aux=True))(adapters)
    l_want = ref_loss(base, TINY, tokens, labels, adapters, wl.scaling)
    g_want = ref_grads(base, TINY, tokens, labels, adapters, wl.scaling)
    assert abs(float(l_got) - float(l_want)) < 1e-5 * abs(float(l_want))
    for got_leaf, want_leaf in zip(jax.tree_util.tree_leaves(g_got),
                                   jax.tree_util.tree_leaves(g_want)):
        assert _rel(got_leaf, want_leaf) < 1e-4
    ids = np.asarray(stats["experts"])
    assert ids.shape == (TINY.num_layers,) + tokens.shape + (TINY.top_k,)
    assert ids.dtype == np.uint8 and ids.max() < TINY.num_experts
    # top-k picks k distinct experts for every token and layer
    assert (np.diff(np.sort(ids, axis=-1), axis=-1) > 0).all()


def test_expert_shares_sum_to_the_uncut_layer():
    """Four disjoint shares of 8 experts, plus the shared MLP counted once,
    give the uncut reference layer."""
    whole = TINY.with_(experts_held=(0, 8))
    k_moe, k_mlp = jax.random.split(jax.random.PRNGKey(4))
    lp = {"moe": init_moe_share(k_moe, whole),
          "shared": init_mlp(k_mlp, whole.d_model, whole.shared_d_ff, "swiglu", jnp.float32)}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 11, TINY.d_model))
    parts, counts, choices = [], [], []
    for lo in range(0, 8, 2):
        moe = {k: (v if k == "router" else v[lo:lo + 2]) for k, v in lp["moe"].items()}
        y, ids = apply_moe_share(moe, x, top_k=whole.top_k, held=(lo, lo + 2))
        parts.append(y)
        choices.append(np.asarray(ids))
        counts.append(int(((choices[-1] >= lo) & (choices[-1] < lo + 2)).sum()))
    sh = lp["shared"]
    with jax.default_matmul_precision("highest"):
        got = sum(parts) + ref.swiglu(x, sh["gate"], sh["up"], sh["down"])
        want = ref.experts(lp["moe"], whole, x) + ref.swiglu(x, sh["gate"], sh["up"], sh["down"])
    assert _rel(got, want) < 1e-5
    # every share routes over all experts alike; each choice lands in one share
    assert all(np.array_equal(c, choices[0]) for c in choices)
    assert sum(counts) == 2 * 11 * whole.top_k


def test_unmerged_adapters_equal_merged_forward():
    wl, base, adapters = _params_and_adapters(7)
    tokens, _ = _tokens(8)
    model = build_model(TINY)
    fwd = jax.jit(model.forward)
    unmerged = fwd(_attached_params(base, adapters, wl.scaling), {"tokens": tokens})
    merged = fwd(_merged_params(base, adapters, wl.scaling), {"tokens": tokens})
    assert _rel(unmerged, merged) < 1e-5


def test_adapter_sites_find_the_mamba_projections():
    wl, base, _ = _params_and_adapters()
    sites = {path for path, _ in _adapter_sites(base["layers"], wl.targets)}
    assert sites == {
        ("mamba", "mixer", "in_proj"), ("mamba", "mixer", "out_proj"),
        ("attention", "mixer", "wq"), ("attention", "mixer", "wk"),
        ("attention", "mixer", "wv"), ("attention", "mixer", "wo"),
    }
    # D = layers x r x (d_in + d_out) summed over the sites
    d, di = TINY.d_model, TINY.d_inner
    p_in = 2 * di + 2 * TINY.ssm_state + TINY.ssm_heads
    hq, hkv = TINY.num_heads * TINY.hd, TINY.num_kv_heads * TINY.hd
    want = (2 * RANK * (d + p_in + di + d)
            + RANK * ((d + hq) + 2 * (d + hkv) + (hq + d)))
    assert wl.proposal_dim({"base": base, "adapters": _params_and_adapters()[2]}) == want


# ---------------------------------------------------------------------------
# one federated round through api.run against a reference round
# ---------------------------------------------------------------------------

K, BAD, STEPS, SEQ, N_K, LR = 6, 2, 2, 12, 4, 0.2


def _afa_screen(G, pn, xi0=2.0, delta_xi=0.5, max_rounds=8):
    """Algorithm 1's screening on the Gram matrix, in float64 (as
    ``bench/reference/fl_afa.afa_screen``)."""
    G, pn = np.asarray(G, np.float64), np.asarray(pn, np.float64)
    norms = np.sqrt(np.maximum(np.diag(G), 0.0))
    kept, xi = np.ones(len(pn), bool), xi0
    for _ in range(max_rounds):
        c = np.where(kept, pn, 0.0)
        c = c / c.sum()
        gc = G @ c
        s = gc / (norms * np.sqrt(c @ gc))
        sk = s[kept]
        mean, med, sd = sk.mean(), np.median(sk), sk.std()
        bad = kept & ((s < med - xi * sd) if mean < med else (s > med + xi * sd))
        if (kept & ~bad).sum() < 2:
            bad[:] = False
        kept &= ~bad
        xi += delta_xi
        if not bad.any():
            break
    c = np.where(kept, pn, 0.0)
    return kept, c / c.sum(), s


def _reference_round(base, adapters0, data, seed, scaling):
    """Round 1: each honest client's two SGD-momentum steps on its device
    minibatch draw (the engine's documented key scheme), the byzantine rows
    ``w_t + N(0, 20^2)``, AFA in float64, the weighted mean."""
    flat0, tdef = jax.tree_util.tree_flatten(adapters0)
    bkey = jax.random.fold_in(jax.random.PRNGKey(seed), _BATCH_STREAM)
    akey = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
    rows = []
    for k in range(K):
        if k < BAD:
            leaves = [l + 20.0 * jax.random.normal(
                jax.random.fold_in(jax.random.fold_in(akey, i), k), l.shape)
                for i, l in enumerate(flat0)]
        else:
            idx = jax.random.randint(jax.random.fold_in(bkey, k), (STEPS, 1), 0, N_K)
            a, mu = adapters0, jax.tree_util.tree_map(jnp.zeros_like, adapters0)
            for s in range(STEPS):
                g = ref_grads(base, TINY, data.x[k][idx[s]], data.y[k][idx[s]], a, scaling)
                mu = jax.tree_util.tree_map(lambda m, g: 0.9 * m + g, mu, g)
                a = jax.tree_util.tree_map(lambda p, m: p - LR * m, a, mu)
            leaves = jax.tree_util.tree_leaves(a)
        rows.append(np.concatenate([np.asarray(l, np.float64).ravel() for l in leaves]))
    rows = np.stack(rows)
    kept, w, sims = _afa_screen(rows @ rows.T, np.full(K, 0.5 * N_K))
    return kept, w @ rows, sims


SEED = 2024


@pytest.fixture(scope="module")
def round1():
    """One round of the tiny hybrid through ``api.run``: its result, the
    span records it left, and its corpus."""
    wl = WL
    seqs = markov_sequences(11, TINY.vocab_size, 40, SEQ + 1)
    sim = SimConfig(num_clients=K, bad_frac=BAD / K, scenario="byzantine", rounds=1,
                    local_epochs=STEPS, batch_size=1, seed=SEED, lr=LR)
    server = ServerConfig(rule="afa", num_clients=K, afa_variant="gram")
    before = len(spans.records())
    out = run(wl, sim, server, data=seqs, samples_per_client=N_K, seq=SEQ, n_test=2,
              keep_round1=True)
    return wl, out, spans.records()[before:], seqs


def test_api_round_matches_the_reference_round(round1):
    from repro.fed.workload import llm_data_from_sequences

    wl, out, _, seqs = round1
    seed = SEED
    got = np.concatenate([np.asarray(l, np.float64).ravel()
                          for l in jax.tree_util.tree_leaves(out["params"]["adapters"])])

    p0 = _init_fn(wl)(jax.random.PRNGKey(seed))
    data = llm_data_from_sequences(seqs, clients=K, samples_per_client=N_K,
                                   n_test=2, seed=seed)
    kept, want, sims = _reference_round(p0["base"], p0["adapters"], data, seed, wl.scaling)
    w0 = np.concatenate([np.asarray(l, np.float64).ravel()
                         for l in jax.tree_util.tree_leaves(p0["adapters"])])
    assert np.array_equal(out["good_mask"][0], kept)
    assert not kept[:BAD].any()
    assert np.abs(out["similarities"][0] - sims).max() < 1e-5
    assert _rel(got - w0, want - w0) < 1e-4
    # one round: the kept round-1 adapters are the final ones
    for a, b in zip(jax.tree_util.tree_leaves(out["params_round1"]["adapters"]),
                    jax.tree_util.tree_leaves(out["params"]["adapters"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_llm_route_spans_and_route_record(round1):
    _, out, recs, _ = round1
    names = [r.name for r in recs]
    for name in ("fed.setup", "fed.llm.call", "fed.llm.wait", "fed.moe.route", "fed.run"):
        assert name in names, name
    setup = next(r for r in recs if r.name == "fed.setup")
    assert setup.attrs["h2d_bytes"] == 2 * 4 * (K * N_K + 2) * SEQ + 2 * 4 * K
    route = next(r for r in recs if r.name == "fed.moe.route").attrs
    # the honest clients trained STEPS steps of one sequence each
    assert out["trained"].tolist() == [[False] * BAD + [True] * (K - BAD)]
    assert out["experts"].shape == (1, K, STEPS, TINY.num_layers, 1, SEQ, TINY.top_k)
    chosen = out["experts"][out["trained"]]
    lo, hi = TINY.held_range
    assert chosen.size == (K - BAD) * STEPS * SEQ * TINY.top_k * TINY.num_layers
    assert route["tokens_held"] == int(((chosen >= lo) & (chosen < hi)).sum())
    assert route["held_share"] == pytest.approx(route["tokens_held"] / chosen.size)
    assert 0 < route["held_share"] < 1 and route["max_over_mean"] >= 1


def test_llm_route_trains_the_honest_rows_and_keeps_k_rows_of_stats(round1):
    """The byzantine rows do not train: ``fed.llm.call`` records K rows and
    K - BAD trained, and the stats come back in K rows, zero in the rows
    that did not train."""
    _, out, recs, _ = round1
    call = next(r for r in recs if r.name == "fed.llm.call")
    assert (call.attrs["rows"], call.attrs["train_rows"]) == (K, K - BAD)
    assert out["experts"].shape[:2] == out["trained"].shape == (1, K)
    assert not out["experts"][~out["trained"]].any()
    assert out["experts"][out["trained"]].any()


# ---------------------------------------------------------------------------
# against the upstream model
# ---------------------------------------------------------------------------


def _hf_to_repo(hf, cfg):
    """The upstream model's weights in the repo's tree (all experts held)."""
    t = lambda w: np.asarray(w.detach().numpy(), np.float32)
    m, f, s = hf.model, cfg.d_ff, cfg.shared_d_ff
    stacks = {"mamba": [], "attention": []}
    for kind, layer in zip(cfg.layer_types, m.layers):
        moe = layer.block_sparse_moe
        win = t(moe.input_linear.weight)
        sin = t(layer.shared_mlp.input_linear.weight)
        lp = {
            "norm_mixer": t(layer.input_layernorm.weight) - 1,
            "norm_ffn": t(layer.post_attention_layernorm.weight) - 1,
            "moe": {"router": t(moe.router.layer.weight).T,
                    "gate": win[:, :f].transpose(0, 2, 1), "up": win[:, f:].transpose(0, 2, 1),
                    "down": t(moe.output_linear.weight).transpose(0, 2, 1)},
            "shared": {"gate": sin[:s].T, "up": sin[s:].T,
                       "down": t(layer.shared_mlp.output_linear.weight).T},
        }
        if kind == "mamba":
            mx = layer.mamba
            lp["mixer"] = {
                "in_proj": t(mx.in_proj.weight).T, "out_proj": t(mx.out_proj.weight).T,
                "conv_w": t(mx.conv1d.weight)[:, 0, :].T, "conv_b": t(mx.conv1d.bias),
                "A_log": t(mx.A_log), "dt_bias": t(mx.dt_bias), "D": t(mx.D),
                "gate_norm_w": t(mx.norm.weight) - 1,
            }
        else:
            at = layer.self_attn
            lp["mixer"] = {"wq": t(at.q_proj.weight).T, "wk": t(at.k_proj.weight).T,
                           "wv": t(at.v_proj.weight).T, "wo": t(at.o_proj.weight).T}
        stacks[kind].append(lp)
    return {
        "embed": t(m.embed_tokens.weight),
        "final_norm": t(m.norm.weight) - 1,
        "layers": {k: jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *v)
                   for k, v in stacks.items() if v},
    }


def test_matches_upstream_granitemoehybrid():
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    cfg = TINY.with_(experts_held=())
    hf_cfg = transformers.GraniteMoeHybridConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.d_model,
        intermediate_size=cfg.d_ff, shared_intermediate_size=cfg.shared_d_ff,
        num_hidden_layers=cfg.num_layers, layer_types=list(cfg.layer_types),
        num_attention_heads=cfg.num_heads, num_key_value_heads=cfg.num_kv_heads,
        num_local_experts=cfg.num_experts, num_experts_per_tok=cfg.top_k,
        mamba_n_heads=cfg.ssm_heads, mamba_d_head=cfg.ssm_head_dim,
        mamba_d_state=cfg.ssm_state, mamba_n_groups=1, mamba_d_conv=cfg.ssm_conv_width,
        mamba_expand=cfg.ssm_expand, mamba_chunk_size=cfg.ssm_chunk, mamba_conv_bias=True,
        mamba_proj_bias=False, embedding_multiplier=cfg.embedding_multiplier,
        residual_multiplier=cfg.residual_multiplier, logits_scaling=cfg.logits_scaling,
        attention_multiplier=cfg.attention_multiplier, position_embedding_type="nope",
        tie_word_embeddings=True, rms_norm_eps=cfg.norm_eps, attention_bias=False,
    )
    torch.manual_seed(0)
    hf = transformers.GraniteMoeHybridForCausalLM(hf_cfg).float().eval()
    with torch.no_grad():  # HF initialises these to constants; vary them
        for layer in hf.model.layers:
            for w in (layer.input_layernorm.weight, layer.post_attention_layernorm.weight):
                w.add_(0.1 * torch.randn_like(w))
            if layer.mamba is not None:
                layer.mamba.norm.weight.add_(0.1 * torch.randn_like(layer.mamba.norm.weight))
                layer.mamba.dt_bias.add_(0.1 * torch.randn_like(layer.mamba.dt_bias))
    tokens, _ = _tokens(13, b=2, length=19)
    with torch.no_grad():
        want = hf(torch.as_tensor(np.asarray(tokens), dtype=torch.long)).logits.numpy()
    params = _hf_to_repo(hf, cfg)
    got = jax.jit(build_model(cfg).forward)(params, {"tokens": tokens})
    assert _rel(got, want) < 2e-5
    assert _rel(ref_logits(params, cfg, tokens), want) < 2e-5
