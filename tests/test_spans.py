"""Host spans (``repro.utils.spans``) and the device scopes of the fused
round: the span tree one segmented ``api.run`` records, the ring's bound,
retraces recorded as ``fed.segment.trace``, and each round phase's
``named_scope`` in the lowered segment program."""

import re

import pytest

from repro.data import make_mnist_like
from repro.fed import ServerConfig, SimConfig
from repro.fed.api import run
from repro.fed.simulator import first_segment
from repro.utils import spans

SEGMENT_CHILDREN = ["fed.segment.layout", "fed.segment.call",
                    "fed.segment.wait", "fed.segment.stitch"]
PHASES = ["local_update", "attack", "pack", "server_step", "apply", "eval"]


@pytest.fixture(scope="module")
def data():
    return make_mnist_like(n_train=600, n_test=100, dim=64)


def _sim(seed=3, **kw):
    """40% byzantine at K = 10: AFA blocks 4 clients mid-run, so the bucket
    drops from 10 to 8 and a second staging happens."""
    base = dict(num_clients=10, bad_frac=0.4, scenario="byzantine", rounds=12,
                local_epochs=1, batch_size=30, hidden=(16,), seed=seed,
                engine="fused", segment_rounds=4, compact=True)
    base.update(kw)
    return SimConfig(**base)


def _run_records(data, sim):
    """Run ``sim`` through ``api.run``; its result and its span records."""
    n0 = max((r.span_id for r in spans.records()), default=0)
    res = run(None, sim, ServerConfig(rule="afa", num_clients=sim.num_clients),
              data=data)
    return res, [r for r in spans.records() if r.span_id > n0]


def test_segmented_run_span_tree(data):
    sim = _sim()
    _, recs = _run_records(data, sim)
    by_id = {r.span_id: r for r in recs}
    (root,) = [r for r in recs if r.parent_id is None]
    assert root.name == "fed.run"
    assert root.attrs == dict(route="simulate", engine="fused", K=10, rounds=12)
    assert {r.run_id for r in recs} == {root.span_id}
    for r in recs:
        assert root.t0 <= r.t0 <= r.t1 <= root.t1
        if r is not root:
            assert r.parent_id in by_id

    def children(rec):
        return [r for r in recs if r.parent_id == rec.span_id]

    top = [r.name for r in sorted(children(root), key=lambda r: r.t0)]
    assert top == ["fed.setup"] + ["fed.segment"] * 3 + ["fed.result"]
    (setup,) = [r for r in recs if r.name == "fed.setup"]
    assert setup.attrs["h2d_bytes"] == 100 * 64 * 4 + 100 * 4  # test x and y

    # a row of the layout: its 60 pool rows in the row map (i32), its
    # length (i32), n_k (f32), the byzantine mask (bool) and its id (u32)
    row_bytes = 60 * 4 + 13
    # the pool: the dataset's training rows, x f32 and y i32, sent once
    pool_bytes = 600 * 64 * 4 + 600 * 4
    segments = sorted((r for r in recs if r.name == "fed.segment"),
                      key=lambda r: r.t0)
    assert [s.attrs["seg_start"] for s in segments] == [0, 4, 8]
    prev_bucket = None
    pools = []
    for seg in segments:
        names = [r.name for r in sorted(children(seg), key=lambda r: r.t0)]
        staged = seg.attrs["bucket"] != prev_bucket
        stage = ["fed.segment.stage"] if staged else []
        assert names == SEGMENT_CHILDREN[:1] + stage + SEGMENT_CHILDREN[1:]
        if staged:
            (st,) = [r for r in children(seg) if r.name == "fed.segment.stage"]
            assert st.attrs["bucket"] == seg.attrs["bucket"]
            assert st.attrs["rows"] == seg.attrs["live"]
            uploaded = pool_bytes if st.attrs["pool"] == "upload" else 0
            assert st.attrs["h2d_bytes"] == (
                row_bytes * seg.attrs["bucket"] + uploaded)
            pools.append(st.attrs["pool"])
        prev_bucket = seg.attrs["bucket"]
    assert segments[0].attrs["bucket"] == 10
    assert segments[-1].attrs["bucket"] == 8  # clients were compacted out
    # the dataset goes to the device at the first staging, and only then
    assert pools == ["upload", "hit"]


def test_stage_records_the_rows_that_train():
    """K = 100, 30% byzantine: the first layout stages 100 rows, of which
    the 70 honest ones train."""
    data = make_mnist_like(n_train=2000, n_test=50, dim=16)
    sim = _sim(num_clients=100, bad_frac=0.3, rounds=4, batch_size=10,
               hidden=(8,))
    _, recs = _run_records(data, sim)
    (stage,) = [r for r in recs if r.name == "fed.segment.stage"]
    assert (stage.attrs["bucket"], stage.attrs["rows"], stage.attrs["train_rows"]) \
        == (100, 100, 70)


def test_llm_call_records_the_rows_that_train():
    """The LLM route with 5 byzantine clients of 16: 11 rows train."""
    from repro.fed.workload import get_workload, simulate_llm
    from repro.models import ModelConfig

    cfg = ModelConfig(name="t-lora", family="dense", num_layers=1, d_model=16,
                      vocab_size=32, num_heads=2, num_kv_heads=1, d_ff=32,
                      block_q=8, block_k=8)
    n0 = max((r.span_id for r in spans.records()), default=0)
    simulate_llm(get_workload("lora", model_cfg=cfg, rank=2), clients=16,
                 byzantine=5, rounds=1, local_steps=1, batch=1,
                 samples_per_client=2, seq=8, n_test=2)
    (call,) = [r for r in spans.records()
               if r.span_id > n0 and r.name == "fed.llm.call"]
    assert (call.attrs["rows"], call.attrs["train_rows"]) == (16, 11)


def test_ring_stays_bounded():
    for i in range(spans.RING_SIZE + 10):
        with spans.span("ring", i=i):
            pass
    recs = spans.records()
    assert len(recs) == spans.RING_SIZE
    assert recs[-1].attrs == {"i": spans.RING_SIZE + 9}
    assert recs[0].span_id == recs[-1].span_id - spans.RING_SIZE + 1


def test_nested_spans_share_the_roots_run_id():
    with spans.span("a") as a_attrs:
        a_attrs["n"] = 1
        with spans.span("b"):
            pass
    with spans.span("c"):
        pass
    a, b, c = sorted(spans.records()[-3:], key=lambda r: r.t0)
    assert (a.name, b.name, c.name) == ("a", "b", "c")
    assert b.parent_id == a.span_id and b.run_id == a.run_id == a.span_id
    assert c.parent_id is None and c.run_id == c.span_id
    assert a.attrs == {"n": 1}


def test_segment_trace_recorded_once_per_shape(data):
    # a segment length no other test uses, so this shape is new here
    sim = _sim(seed=5, rounds=10, segment_rounds=5)
    _, first = _run_records(data, sim)
    traces = [r for r in first if r.name == "fed.segment.trace"]
    shapes = {(s.attrs["bucket"], s.attrs["seg_len"])
              for s in first if s.name == "fed.segment"}
    assert sorted((t.attrs["bucket"], t.attrs["seg_len"]) for t in traces) \
        == sorted(shapes)
    for t in traces:
        assert [r.name for r in first if r.span_id == t.parent_id] \
            == ["fed.segment.call"]
    _, second = _run_records(data, sim)
    assert [r for r in second if r.name == "fed.segment.trace"] == []
    assert [r for r in second if r.name == "fed.segment"]


def test_segment_program_holds_each_phase_scope(data):
    """The compiled program's op metadata carries each phase's scope under
    the round body: the name a device trace's ops are attributed by."""
    seg_fn, args = first_segment(data, _sim(), ServerConfig(rule="afa",
                                                           num_clients=10))
    text = seg_fn.lower(*args).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    for phase in PHASES:
        assert any(f"/while/body/closed_call/{phase}/" in n for n in names), phase
