"""The trace reduction, on a small trace recorded on the CPU from jitted
functions named as the engine's programs are."""

import jax
import jax.numpy as jnp
import pytest

import trace_reduce as tr

LAYERS = {"binary steps": ["_gather_core"],
          "partition and layout": ["stable_order"],
          "fused root": ["fused_count3_*"]}


@jax.jit
def stable_order(x):
    order = jnp.argsort(x, stable=True)
    return order, x[order]


@jax.jit
def _gather_core(x, order):
    return x[order] * 3 + 1


@jax.jit
def fused_count3_linear(x):
    return jnp.sum((x[:, None] == x[None, :2048]).astype(jnp.int32))


@jax.jit
def unnamed(x):
    return jnp.cumsum(x)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    log_dir = tmp_path_factory.mktemp("trace")
    x = jnp.arange(200_000, dtype=jnp.int32)[::-1] % 9973
    for f in (lambda: stable_order(x), lambda: _gather_core(x, x),
              lambda: fused_count3_linear(x[:4096]), lambda: unnamed(x)):
        jax.block_until_ready(f())
    jax.profiler.start_trace(str(log_dir))
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench.query"):
            order, s = stable_order(x)
            jax.block_until_ready(_gather_core(s, order))
            jax.block_until_ready(fused_count3_linear(s[:4096]))
            jax.block_until_ready(unnamed(s))
    jax.profiler.stop_trace()
    return tr.find_xplane(str(log_dir))


def test_module_names():
    assert tr.module_key("jit_stable_order(12)") == "stable_order"
    assert tr.module_key("jit__gather_core.3") == "_gather_core"
    assert tr.module_key("fused_count3_star") == "fused_count3_star"
    assert tr.layer_of("fused_count3_star", LAYERS) == "fused root"
    assert tr.layer_of("jit_add", LAYERS) == "other"


def test_union_and_gaps_on_synthetic_ops():
    ops = [tr.Op("d", "a", "stable_order", 10, 20),
           tr.Op("d", "b", "stable_order", 15, 30),
           tr.Op("d", "c", "_gather_core", 50, 60)]
    spans = [tr.Span("bench.query", 0, 100)]
    red = tr.reduce(ops, spans, LAYERS)
    assert red.busy_s == pytest.approx(30e-9)
    assert red.window_s == pytest.approx(100e-9)
    assert red.idle_share == pytest.approx(0.7)
    # overlapping ops count once: layers add up to the busy time
    assert red.layer_s["partition and layout"] == pytest.approx(20e-9)
    gaps = dict((g[0], g[1]) for g in red.idle_gaps)
    assert gaps["bench.query: stable_order -> _gather_core"] == \
        pytest.approx(20e-9)
    assert gaps["bench.query: _gather_core -> end"] == pytest.approx(40e-9)


def test_nested_ops_count_their_self_time():
    # a while op whose body ops are traced inside it, as on a TPU
    ops = [tr.Op("d", "%while.3", "fused_count3_star", 0, 100),
           tr.Op("d", "%fusion.1", "fused_count3_star", 10, 40),
           tr.Op("d", "%fusion.2", "fused_count3_star", 50, 60),
           tr.Op("d", "%sort", "stable_order", 120, 150)]
    red = tr.reduce(ops, [tr.Span("bench.query", 0, 200)], LAYERS)
    assert red.busy_s == pytest.approx(130e-9)
    assert red.layer_s["fused root"] == pytest.approx(100e-9)
    assert dict(red.top_ops)["fused_count3_star:%while.3"] == \
        pytest.approx(60e-9)


def test_recorded_trace(recorded):
    ops, spans = tr.read_events(recorded, allow_cpu=True)
    assert [s.name for s in spans] == ["bench.query", "bench.query"]
    red = tr.reduce(ops, spans, LAYERS)
    assert {"stable_order", "_gather_core", "fused_count3_linear",
            "unnamed"} <= set(red.module_s)
    assert red.layer_s["fused root"] > 0
    assert red.layer_s["other"] >= red.module_s["unnamed"]
    assert 0 < red.busy_s <= red.window_s
    assert sum(red.layer_s.values()) == pytest.approx(
        sum(red.module_s.values()))
    assert len(red.top_ops) <= 10 and len(red.idle_gaps) <= 10
    assert all(g[0].startswith(("bench.query", "between requests"))
               for g in red.idle_gaps)


def test_trace_without_spans_is_refused():
    with pytest.raises(ValueError):
        tr.reduce([], [], LAYERS)


def test_trace_without_device_ops_is_refused(recorded):
    # a CPU trace has host threads only: no stand-in unless asked for
    with pytest.raises(ValueError, match="no device XLA Ops"):
        tr.read_events(recorded)


def test_window_without_ops_is_refused():
    ops = [tr.Op("d", "a", "stable_order", 500, 600)]
    with pytest.raises(ValueError, match="no device operation"):
        tr.reduce(ops, [tr.Span("bench.query", 0, 100)], LAYERS)
