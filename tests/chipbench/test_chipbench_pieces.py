"""The yardstick's parts on inputs with known answers: the traffic
generator, the trace reduction on a small synthetic trace, the cost
functions on published shapes, the readers, the last-line keys."""

from __future__ import annotations

import json
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from chipbench import cost, manifest, readers, stats, text, trace_reduce, traffic  # noqa: E402
from chipbench.cost import decoder as cost_decoder  # noqa: E402

MIX = {
    "route": "/v2/answer",
    "arrivals": {"rate_per_s": 2.0, "draw_seed": 25},
    "payload": {"field": "prompt", "words": [8, 20], "about_documents": True, "extra": {"x": 1}},
}


def test_schedule_is_the_seeds_and_every_seed_gets_the_same_sizes_and_arrivals():
    docs = text.make_documents(50, 3, (46, 58))
    a = traffic.make_schedule(MIX, 2**31 + 77, 30.0, docs)
    b = traffic.make_schedule(MIX, 2**31 + 77, 30.0, docs)
    c = traffic.make_schedule(MIX, 5, 30.0, docs)
    assert a == b and a != c and len(a) == len(c) == 60
    assert a[0]["due_s"] == 0.0 and all(0 <= r["due_s"] < 30.0 for r in a)
    # the arrivals are the mix's own draw, whatever the seed; the questions are the seed's
    assert [r["due_s"] for r in a] == [r["due_s"] for r in c]
    assert [r["payload"]["prompt"] for r in a] != [r["payload"]["prompt"] for r in c]
    words = lambda s: sorted(len(r["payload"]["prompt"].split()) for r in s)  # noqa: E731
    assert words(a) == words(c) and min(words(a)) == 8 and max(words(a)) == 20
    assert a[0]["payload"]["x"] == 1 and a[0]["route"] == "/v2/answer"
    other = traffic.make_schedule({**MIX, "arrivals": {"rate_per_s": 2.0, "draw_seed": 26}}, 5, 30.0, docs)
    assert [r["due_s"] for r in other] != [r["due_s"] for r in c]


def test_exponential_gaps_add_up_and_documents_keep_their_lengths():
    due = traffic.due_times({"rate_per_s": 2.0, "draw_seed": 1}, 50.0)
    gaps = [y - x for x, y in zip(due, due[1:])]
    assert len(due) == 100 and due[0] == 0.0 and due[-1] < 50.0 and min(gaps) > 0
    assert max(gaps) / (50.0 / 100) > 3  # a tail, not a comb
    mean = sum(gaps) / len(gaps)
    assert 0.7 < (sum((g - mean) ** 2 for g in gaps) / len(gaps)) ** 0.5 / mean < 1.3  # exponential
    tok = text.HashTokenizer(30522)
    for seed in (1, 2**31 + 5):
        lens = sorted(len(tok.encode(d, 512)) for d in text.make_documents(200, seed, (46, 58)))
        assert lens[0] >= 51 and lens[-1] <= 63  # inside the encoder's 64 bucket


def synthetic_planes():
    ms = 1e6
    return [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [("x", 0.0, 9 * ms)]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ("jit__decode(123)", 0.0, 4 * ms), ("jit__prefill(9)", 5 * ms, 2 * ms),
                ("jit__decode(123)", 8 * ms, 2 * ms)]},
            {"name": "XLA Ops", "events": [
                ("fusion.1", 0.0, 3 * ms), ("fusion.2", 2 * ms, 2 * ms),  # overlap: union 4 ms
                ("dot.7", 5 * ms, 2 * ms), ("fusion.1", 8 * ms, 2 * ms)]},
        ]},
    ]


def test_trace_reduction_on_a_synthetic_trace():
    out = trace_reduce.reduce(synthetic_planes(), window_s=0.010)
    assert out["busy_s"] == pytest.approx(0.008) and out["window_s"] == 0.010
    assert out["modules"]["jit__decode"] == {"seconds": pytest.approx(0.006), "runs": 2}
    assert out["modules"]["jit__prefill"]["runs"] == 1
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.005)]
    assert [g[1] for g in out["idle_gaps"]] == [pytest.approx(0.001), pytest.approx(0.001)]
    with pytest.raises(ValueError):
        trace_reduce.reduce(synthetic_planes()[:1])


def mistral_7b():
    cfg = json.loads((REPO / "chipbench/configs/mistral7b-bge-rag.json").read_text())
    return {**cfg, "num_hidden_layers": 32}


def test_cost_functions_on_published_shapes():
    cfg = mistral_7b()
    # Mistral-7B: 7.24 B parameters, 131 M of them the embedding table
    assert cost_decoder.matmul_params(cfg) == pytest.approx(7.24e9 - 0.131e9, rel=0.002)
    assert cost_decoder.kv_bytes_per_token(cfg) == 2 * 32 * 8 * 128 * 2
    step = cost_decoder.decode_step(cfg, rows=8, context=500)
    assert step["bytes"] == pytest.approx(7.11e9 * 2 + 8 * 500 * 131072, rel=0.002)
    assert step["flops"] == pytest.approx(2 * 7.11e9 * 8, rel=0.02)
    peak = manifest.peak("TPU v5 lite")
    seconds, bound = cost.least_seconds(step, peak)
    assert bound == "bandwidth" and seconds == pytest.approx(step["bytes"] / 819e9)
    chunk = cost.lookup("decoder.prefill_chunk")(cfg, rows=1, chunk=32, context=200)
    assert chunk["bytes"] == pytest.approx(6.98e9 * 2 + 232 * 131072, rel=0.002)
    assert chunk["flops"] == pytest.approx(2 * 6.98e9 * 32, rel=0.02)
    with pytest.raises(KeyError):
        manifest.peak("no such chip")


def reader_context():
    before = {"executor": {"callables": {"encoder:a": {"dispatches": 2}, "indexing:t": {"dispatches": 1}}},
              "histograms": {"h": {"sum": 10.0, "count": 2}}, "scalars": {"generate.tokens": 10.0}}
    after = {"executor": {"callables": {"encoder:a": {"dispatches": 6}, "encoder:b": {"dispatches": 3},
                                        "indexing:t": {"dispatches": 1}}},
             "histograms": {"h": {"sum": 40.0, "count": 8}}, "scalars": {"generate.tokens": 74.0}}
    return {"before": before, "after": after, "span_s": 2.0, "setup_s": 80.0, "latencies_ms": [1.0, 2.0, 3.0, 4.0],
            "work": {"decoder_tokens": 1000.0}, "trace": trace_reduce.reduce(synthetic_planes(), 0.010),
            "sections": {"decoder": mistral_7b(), "serving": {"k": 6}}, "peak": manifest.peak("TPU v5 lite")}


@pytest.mark.parametrize("spec,expected", [
    ({"reader": "snapshot", "path": ["executor", "callables", "re:^encoder:", "dispatches"]}, 7.0),
    ({"reader": "snapshot", "path": ["scalars", "generate.tokens"]}, 64.0),
    ({"reader": "snapshot", "path": ["executor", "nothing", "here"]}, None),
    ({"reader": "registry", "histogram": "h"}, 5.0),
    ({"reader": "registry", "histogram": "absent"}, None),
    ({"reader": "window", "stat": "latency_ms", "percentile": 50}, 2.0),
    ({"reader": "window", "stat": "latency_ms", "percentile": 0}, 1.0),
    ({"reader": "window", "stat": "latency_mean_ms"}, 2.5),
    ({"reader": "window", "stat": "setup_s"}, 80.0),
    ({"reader": "derived", "op": "sub", "terms": [{"reader": "window", "stat": "latency_mean_ms"}, {"reader": "window", "stat": "latency_ms", "percentile": 0}]}, 1.5),
    ({"reader": "derived", "op": "div", "terms": [{"reader": "window", "stat": "work", "key": "decoder_tokens"}, 4.0]}, 250.0),
    ({"reader": "derived", "op": "div", "terms": [1.0, {"reader": "snapshot", "path": ["executor", "callables", "indexing:t", "dispatches"]}]}, None),
    ({"reader": "trace_module", "module": "^jit__absent$", "cost": "decoder.decode_step", "section": "decoder"}, None),
    ({"reader": "trace_idle"}, 20.0),
], ids=lambda v: v.get("reader", "") + ":" + str(v.get("path", v.get("histogram", v.get("module", v.get("stat", v.get("op", "")))))) if isinstance(v, dict) else None)
def test_reader_kinds(spec, expected):
    got = readers.evaluate(spec, reader_context())
    assert got == (pytest.approx(expected) if expected is not None else None)


def test_roofline_and_mfu_readers_use_the_cost_functions():
    ctx = reader_context()
    roof = readers.evaluate(
        {"reader": "trace_module", "module": "^jit__decode$", "cost": "decoder.decode_step",
         "section": "decoder", "args": {"rows": 8.0, "context": 500.0}}, ctx)
    least = cost.least_seconds(cost_decoder.decode_step(mistral_7b(), rows=8, context=500), ctx["peak"])[0]
    assert roof == pytest.approx(100 * least / 0.003)
    mfu = readers.evaluate(
        {"reader": "mfu", "cost": "decoder.tokens", "section": "decoder",
         "args": {"tokens": {"reader": "window", "stat": "work", "key": "decoder_tokens"}}}, ctx)
    assert mfu == pytest.approx(100 * 2 * cost_decoder.matmul_params(mistral_7b()) * 1000 / 2.0 / 197e12)
    ctx["trace"] = None  # nothing to read: nothing returned, never 0
    assert readers.evaluate({"reader": "trace_idle"}, ctx) is None


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert stats.percentile(values, 50) == 50 and stats.percentile(values, 90) == 90
    assert stats.percentile(values, 100) == 100 and stats.percentile([], 50) is None
