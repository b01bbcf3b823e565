"""Packed (K, D) aggregation path: PackSpec round-trips, packed-vs-tree
dispatch equality for every registered rule, the packed fused-trajectory
bit-identity, and the three-way kernel policy (pallas / jnp / interpret).

The hypothesis property tests guard the layout contract over arbitrary
mixed-dtype pytrees and random masks; the parametrized tests cover the same
surface deterministically so the file is useful even where hypothesis is not
installed (they do not importorskip at module level on purpose)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    RULES,
    RuleOptions,
    dispatch_rule,
    dispatch_rule_tree,
    trimmed_mean_aggregate,
)
from repro.fed.server import ServerConfig, init_server_state, server_step
from repro.kernels.policy import (
    ENV_VAR,
    explicit_kernel_request,
    requested_policy,
    resolve_kernel_mode,
)
from repro.utils.trees import pack_spec, pack_stack, unpack_stack

RNG = np.random.default_rng(7)


def _stacked(K=6, dtype=np.float32):
    return {
        "w": jnp.asarray(RNG.normal(size=(K, 5, 4)).astype(dtype)),
        "b": jnp.asarray(RNG.normal(size=(K, 4)).astype(dtype)),
    }


# ----------------------------- pack / unpack ---------------------------------


def test_pack_stack_layout_and_roundtrip():
    K = 5
    stacked = _stacked(K)
    spec = pack_spec(stacked, stacked=True)
    packed = pack_stack(stacked, spec)
    assert packed.shape == (K, 5 * 4 + 4) and spec.dim == 24
    assert packed.dtype == jnp.float32
    # columns in tree_leaves order ("b" before "w" for a dict), row-major
    np.testing.assert_array_equal(
        np.asarray(packed[:, :4]), np.asarray(stacked["b"])
    )
    np.testing.assert_array_equal(
        np.asarray(packed[:, 4:]), np.asarray(stacked["w"]).reshape(K, -1)
    )
    rt = unpack_stack(packed, spec)
    for k in stacked:
        assert rt[k].dtype == stacked[k].dtype
        np.testing.assert_array_equal(np.asarray(rt[k]), np.asarray(stacked[k]))
    # a (D,) vector unpacks to the row template (the aggregate path)
    row = unpack_stack(packed[0], spec)
    assert row["w"].shape == (5, 4) and row["b"].shape == (4,)
    np.testing.assert_array_equal(np.asarray(row["w"]), np.asarray(stacked["w"])[0])


def test_pack_spec_is_cached_and_hashable():
    a, b = _stacked(4), _stacked(4)
    sa, sb = pack_spec(a, stacked=True), pack_spec(b, stacked=True)
    assert sa is sb  # same structure/shapes/dtypes -> one cached spec
    assert hash(sa) == hash(sb)  # static-arg eligible
    assert pack_spec(_stacked(4, np.float16), stacked=True) is not sa


def test_pack_mixed_dtypes_promote_and_roundtrip_exact():
    """Mixed bf16/f32 trees pack in the promoted dtype (f32) and unpack back
    to each leaf's recorded dtype exactly — f32 represents every bf16."""
    K = 4
    stacked = {
        "lo": jnp.asarray(RNG.normal(size=(K, 3, 2)), jnp.bfloat16),
        "hi": jnp.asarray(RNG.normal(size=(K, 5)).astype(np.float32)),
    }
    spec = pack_spec(stacked, stacked=True)
    packed = pack_stack(stacked, spec)
    assert packed.dtype == jnp.float32
    rt = unpack_stack(packed, spec)
    assert rt["lo"].dtype == jnp.bfloat16 and rt["hi"].dtype == jnp.float32
    for k in stacked:
        np.testing.assert_array_equal(
            np.asarray(rt[k], np.float32), np.asarray(stacked[k], np.float32)
        )


def test_pack_roundtrip_property():
    """Hypothesis: pack -> unpack is the identity for arbitrary floating
    mixed-dtype stacked pytrees (shapes, dtypes, nesting all drawn)."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=30, deadline=None)
    @hyp.given(data=st.data())
    def run(data):
        K = data.draw(st.integers(2, 5), label="K")
        n_leaves = data.draw(st.integers(1, 4), label="n_leaves")
        rng = np.random.default_rng(data.draw(st.integers(0, 2**31 - 1)))
        tree = {}
        for i in range(n_leaves):
            ndim = data.draw(st.integers(0, 3), label=f"ndim{i}")
            shape = tuple(
                data.draw(st.integers(1, 4), label=f"dim{i}_{j}")
                for j in range(ndim)
            )
            dt = data.draw(
                st.sampled_from([jnp.float32, jnp.bfloat16, jnp.float16]),
                label=f"dtype{i}",
            )
            tree[f"leaf{i}"] = jnp.asarray(
                rng.normal(size=(K,) + shape), dt
            )
        spec = pack_spec(tree, stacked=True)
        packed = pack_stack(tree, spec)
        assert packed.shape == (K, spec.dim)
        rt = unpack_stack(packed, spec)
        for k in tree:
            assert rt[k].dtype == tree[k].dtype and rt[k].shape == tree[k].shape
            np.testing.assert_array_equal(
                np.asarray(rt[k], np.float32), np.asarray(tree[k], np.float32)
            )

    run()


# --------------------- packed dispatch == tree dispatch ----------------------


MASKS = {
    "all_live": [True] * 6,
    "partial": [True, False, True, True, False, True],
    "single": [False] * 5 + [True],
    "empty": [False] * 6,
}


@pytest.mark.parametrize("rule", sorted(RULES))
@pytest.mark.parametrize("mask_name", sorted(MASKS))
def test_packed_tree_dispatch_equals_matrix_dispatch(rule, mask_name):
    """The packed tree dispatch must be bit-identical to calling the matrix
    dispatch on pack_stack(tree) — packing is the ONLY thing it adds."""
    K = 6
    stacked = _stacked(K)
    n_k = jnp.asarray(RNG.uniform(50, 150, K).astype(np.float32))
    p_k = jnp.full((K,), 0.5, jnp.float32)
    mask = jnp.asarray(MASKS[mask_name])
    opts = RuleOptions()
    mat = dispatch_rule(rule, pack_stack(stacked), n_k, p_k, mask, opts)
    pk = dispatch_rule_tree(rule, stacked, n_k, p_k, mask, opts, layout="packed")
    flat = np.concatenate(
        [np.asarray(l).ravel() for l in jax.tree_util.tree_leaves(pk.aggregate)]
    )
    np.testing.assert_array_equal(flat, np.asarray(mat.aggregate))
    np.testing.assert_array_equal(
        np.asarray(pk.good_mask), np.asarray(mat.good_mask)
    )
    assert bool(np.asarray(pk.all_blocked)) == bool(np.asarray(mat.all_blocked))


@pytest.mark.parametrize("rule", sorted(RULES))
def test_packed_dispatch_agrees_with_leaf_dispatch(rule):
    """Packed vs the legacy per-leaf layout: identical selections and (up to
    per-leaf vs full-D reduction order for AFA's native tree form) the same
    aggregate.  The 8 matrix-only rules are bit-identical — their leaf path
    flattened to the same buffer all along."""
    K = 6
    stacked = _stacked(K)
    n_k = jnp.asarray(RNG.uniform(50, 150, K).astype(np.float32))
    p_k = jnp.full((K,), 0.5, jnp.float32)
    mask = jnp.asarray(MASKS["partial"])
    opts = RuleOptions()
    pk = dispatch_rule_tree(rule, stacked, n_k, p_k, mask, opts, layout="packed")
    lf = dispatch_rule_tree(rule, stacked, n_k, p_k, mask, opts, layout="leaf")
    np.testing.assert_array_equal(
        np.asarray(pk.good_mask), np.asarray(lf.good_mask)
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(pk.aggregate),
        jax.tree_util.tree_leaves(lf.aggregate),
    ):
        if RULES[rule].tree_fn is None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6
            )


def test_packed_dispatch_random_mask_property():
    """Hypothesis: packed == matrix dispatch bitwise for every rule under
    random masks and update values."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=20, deadline=None)
    @hyp.given(
        seed=st.integers(0, 2**31 - 1),
        mask_bits=st.lists(st.booleans(), min_size=6, max_size=6),
        rule=st.sampled_from(sorted(RULES)),
    )
    def run(seed, mask_bits, rule):
        rng = np.random.default_rng(seed)
        K = 6
        stacked = {
            "w": jnp.asarray(rng.normal(size=(K, 5, 4)).astype(np.float32)),
            "b": jnp.asarray(rng.normal(size=(K, 4)).astype(np.float32)),
        }
        n_k = jnp.asarray(rng.uniform(50, 150, K).astype(np.float32))
        p_k = jnp.asarray(rng.uniform(0.1, 0.9, K).astype(np.float32))
        mask = jnp.asarray(mask_bits)
        opts = RuleOptions()
        mat = dispatch_rule(rule, pack_stack(stacked), n_k, p_k, mask, opts)
        pk = dispatch_rule_tree(rule, stacked, n_k, p_k, mask, opts,
                                layout="packed")
        flat = np.concatenate([
            np.asarray(l).ravel()
            for l in jax.tree_util.tree_leaves(pk.aggregate)
        ])
        np.testing.assert_array_equal(flat, np.asarray(mat.aggregate))
        np.testing.assert_array_equal(
            np.asarray(pk.good_mask), np.asarray(mat.good_mask)
        )

    run()


def test_server_step_packed_layout_equals_tree_layout():
    """server_step on a pre-packed buffer (the fused round body's route) must
    match the tree layout bit for bit — state transitions included."""
    K = 6
    stacked = _stacked(K)
    n_k = jnp.full((K,), 100.0, jnp.float32)
    mask = jnp.asarray(MASKS["partial"])
    cfg = ServerConfig(rule="afa", num_clients=K)
    from repro.fed.server import make_rule_options

    opts = make_rule_options(cfg, K)
    s_t, r_t = server_step(
        init_server_state(K), stacked, n_k, mask,
        rule="afa", opts=opts, layout="tree",
    )
    s_p, r_p = server_step(
        init_server_state(K), pack_stack(stacked), n_k, mask,
        rule="afa", opts=opts, layout="packed",
    )
    np.testing.assert_array_equal(
        np.asarray(r_t.good_mask), np.asarray(r_p.good_mask)
    )
    flat = np.concatenate([
        np.asarray(l).ravel()
        for l in jax.tree_util.tree_leaves(r_t.aggregate)
    ])
    np.testing.assert_array_equal(flat, np.asarray(r_p.aggregate))
    for a, b in zip(
        jax.tree_util.tree_leaves(s_t), jax.tree_util.tree_leaves(s_p)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------- packed fused trajectory bit-identity --------------------


@pytest.fixture(scope="module")
def traj_data():
    from repro.data import make_mnist_like

    return make_mnist_like(n_train=800, n_test=200, dim=64)


def test_fused_trajectory_packed_bit_identical_to_tree(traj_data):
    """Threading the packed layout through the scan body (pack once per
    round) vs packing inside the dispatch is a pure layout change: identical
    (test_error, good_mask, blocked) trajectories, bit for bit, on a
    byzantine workload where AFA blocks clients mid-run."""
    from repro.fed import SimConfig
    from repro.fed import run as fed_run

    def run(layout):
        sim = SimConfig(
            num_clients=8, bad_frac=0.4, scenario="byzantine", rounds=6,
            local_epochs=2, batch_size=64, hidden=(32, 16), dropout=True,
            seed=3, engine="fused",
        )
        return fed_run(
            None, sim,
            ServerConfig(rule="afa", num_clients=8, agg_layout=layout),
            data=traj_data,
        )

    pk, tr = run("packed"), run("tree")
    np.testing.assert_array_equal(
        np.asarray(pk.test_error), np.asarray(tr.test_error)
    )
    np.testing.assert_array_equal(
        np.stack(pk.good_mask_history), np.stack(tr.good_mask_history)
    )
    np.testing.assert_array_equal(pk.blocked_round, tr.blocked_round)
    # the scenario engages blocking, so the equality covers state absorption
    assert (pk.blocked_round > 0).any()

    # vs the legacy leaf layout: AFA's native tree form accumulates per leaf,
    # so its aggregates differ from the packed matrix form in FP reduction
    # order (allclose, not bitwise) — but on the fixed seed every DECISION
    # (screening good_mask, blocking round) must come out identical, and the
    # error trajectory must agree to float tolerance
    lf = run("leaf")
    np.testing.assert_array_equal(
        np.stack(pk.good_mask_history), np.stack(lf.good_mask_history)
    )
    np.testing.assert_array_equal(pk.blocked_round, lf.blocked_round)
    np.testing.assert_allclose(
        np.asarray(pk.test_error), np.asarray(lf.test_error), rtol=0, atol=1e-4
    )


# --------------------------- kernel policy -----------------------------------


def test_resolve_kernel_mode_defaults(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    assert resolve_kernel_mode(False) == "jnp"
    assert resolve_kernel_mode(None) == "jnp"
    backend = jax.default_backend()
    # auto never selects pallas-gpu: the Triton route is explicit opt-in
    # (single-block geometries only — see kernels/policy.py)
    expected = "pallas" if backend == "tpu" else "jnp"
    assert resolve_kernel_mode(True) == expected
    assert resolve_kernel_mode("interpret") == "interpret"
    assert resolve_kernel_mode("pallas") == "pallas"
    assert resolve_kernel_mode("pallas-gpu") == "pallas-gpu"
    assert resolve_kernel_mode("jnp") == "jnp"
    assert explicit_kernel_request(True) is None
    assert explicit_kernel_request("interpret") == "interpret"


def test_resolve_kernel_mode_env_override(monkeypatch):
    monkeypatch.setenv(ENV_VAR, "interpret")
    assert requested_policy() == "interpret"
    assert resolve_kernel_mode(True) == "interpret"
    assert resolve_kernel_mode(False) == "jnp"  # env never force-enables
    assert explicit_kernel_request(True) == "interpret"
    monkeypatch.setenv(ENV_VAR, "bogus")
    with pytest.raises(ValueError, match="REPRO_KERNELS"):
        requested_policy()


def test_trimmed_mean_kernel_route_matches_reference(monkeypatch):
    """trimmed_mean used to raise NotImplementedError on an explicit kernel
    demand; it now routes through the masked rank-trim kernel
    (kernels/trimmed_mean.py), which must match the sort-based reference —
    masked, unmasked, and in the empty-trim-window degradation."""
    monkeypatch.delenv(ENV_VAR, raising=False)
    K, d = 9, 33
    U = jnp.asarray(RNG.normal(size=(K, d)).astype(np.float32))
    mask = jnp.asarray([True] * 6 + [False] * 3)
    for m in (None, mask):
        ref = trimmed_mean_aggregate(U, mask=m, trim=2, use_kernels=False)
        krn = trimmed_mean_aggregate(U, mask=m, trim=2, use_kernels="interpret")
        np.testing.assert_allclose(
            np.asarray(krn.aggregate), np.asarray(ref.aggregate),
            rtol=1e-5, atol=1e-5,
        )
    # m <= 2*trim: both must degrade to the masked mean, not a zero aggregate
    small = jnp.asarray([True] * 3 + [False] * 6)
    ref = trimmed_mean_aggregate(U, mask=small, trim=2, use_kernels=False)
    krn = trimmed_mean_aggregate(U, mask=small, trim=2, use_kernels="interpret")
    np.testing.assert_allclose(
        np.asarray(krn.aggregate), np.asarray(ref.aggregate), rtol=1e-5, atol=1e-5
    )
    assert float(jnp.abs(krn.aggregate).sum()) > 0.0


def test_trimmed_mean_kernel_under_env_pinned_mode(monkeypatch):
    """use_kernels=True while $REPRO_KERNELS pins a kernel mode engages the
    kernel route (this combination used to raise).  Fresh `trim` value ->
    fresh trace, so a cached jit signature cannot mask a routing bug."""
    monkeypatch.setenv(ENV_VAR, "interpret")
    K, d = 8, 16
    U = jnp.asarray(RNG.normal(size=(K, d)).astype(np.float32))
    ref = trimmed_mean_aggregate(U, trim=3, use_kernels=False)
    krn = trimmed_mean_aggregate(U, trim=3, use_kernels=True)
    np.testing.assert_allclose(
        np.asarray(krn.aggregate), np.asarray(ref.aggregate), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize(
    "rule", ["fa", "mkrum", "norm_clip", "afa", "comed", "trimmed_mean", "bulyan"]
)
def test_interpret_mode_dispatch_matches_jnp_reference(rule):
    """The dispatch-level kernel route, executed via the Pallas interpreter
    on CPU, must agree with the jnp reference path — this is the coverage
    the old TPU-only gate never had."""
    K = 6
    stacked = _stacked(K)
    n_k = jnp.full((K,), 100.0, jnp.float32)
    p_k = jnp.full((K,), 0.5, jnp.float32)
    mask = jnp.asarray(MASKS["partial"])
    ref = dispatch_rule_tree(
        rule, stacked, n_k, p_k, mask, RuleOptions(use_kernels="jnp")
    )
    krn = dispatch_rule_tree(
        rule, stacked, n_k, p_k, mask, RuleOptions(use_kernels="interpret")
    )
    np.testing.assert_array_equal(
        np.asarray(ref.good_mask), np.asarray(krn.good_mask)
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(ref.aggregate),
        jax.tree_util.tree_leaves(krn.aggregate),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5
        )


def test_afa_gram_variant_interpret_kernels_match_reference():
    """variant="gram" + a kernel mode now takes the FUSED screening launch
    (kernel_launch="fused", the default) — bit-identical to the jnp gram
    reference on the interpret route; kernel_launch="chained" keeps the PR-4
    per-op launches, allclose as before."""
    from repro.core import AFAConfig, afa_aggregate

    K, d = 8, 64
    U = jnp.asarray(RNG.normal(size=(K, d)).astype(np.float32))
    n_k = jnp.full((K,), 100.0, jnp.float32)
    p_k = jnp.full((K,), 0.5, jnp.float32)
    for variant in ("iterative", "gram"):
        ref = afa_aggregate(
            U, n_k, p_k, config=AFAConfig(variant=variant, use_kernels="jnp")
        )
        krn = afa_aggregate(
            U, n_k, p_k,
            config=AFAConfig(variant=variant, use_kernels="interpret"),
        )
        np.testing.assert_array_equal(
            np.asarray(ref.good_mask), np.asarray(krn.good_mask)
        )
        if variant == "gram":  # fused route: exact shapes, bitwise
            np.testing.assert_array_equal(
                np.asarray(ref.aggregate), np.asarray(krn.aggregate)
            )
        else:
            np.testing.assert_allclose(
                np.asarray(ref.aggregate), np.asarray(krn.aggregate),
                rtol=1e-5, atol=1e-5,
            )
        if variant == "gram":
            chained = afa_aggregate(
                U, n_k, p_k,
                config=AFAConfig(variant=variant, use_kernels="interpret",
                                 kernel_launch="chained"),
            )
            np.testing.assert_array_equal(
                np.asarray(ref.good_mask), np.asarray(chained.good_mask)
            )
            np.testing.assert_allclose(
                np.asarray(ref.aggregate), np.asarray(chained.aggregate),
                rtol=1e-5, atol=1e-5,
            )
