"""``benchmark/lib/region_reduce.py`` against recorded traces.

``data/small_regions_v5e.xplane.pb`` was recorded on a TPU v5 lite by this
file's ``__main__`` (``python3 benchmark/tests/test_region_reduce.py
<directory>``): three calls of :func:`toy_step`, a jitted train step of a
toy stack with the program's regions in it: a ``lax.scan`` of
``jax.checkpoint``ed layers (an ``areal.attn`` half and an ``areal.mlp``
half, 256 x 256 bfloat16 products), an ``areal.loss``, ``value_and_grad``
and an ``areal.optimizer`` update.  The expected numbers were read off the
raw events (the ``XLA Ops`` line's durations by instruction, the
instructions' ``tf_op`` by eye), not from the code under test.
"""

import json
import os
import re
import shutil
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(HERE, "data")
FIXTURE = os.path.join(DATA, "small_regions_v5e.xplane.pb")
OLD_FIXTURE = os.path.join(DATA, "small_v5e.xplane.pb")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# run.py loaded by path, and BENCHMARK.json: as the harness's own tests
from benchmark.tests.test_benchmark import run, spec  # noqa: E402,F401


def toy_step():
    """``(jitted step, its arguments)``: what the fixture ran."""
    import jax
    import jax.numpy as jnp

    def layer(x, w):
        with jax.named_scope("areal.attn"):
            x = x + jnp.tanh(x @ w["a"])
        with jax.named_scope("areal.mlp"):
            return x + jax.nn.silu(x @ w["g"]) @ w["d"]

    def loss(ws, x):
        y, _ = jax.lax.scan(
            jax.checkpoint(lambda c, w: (layer(c, w), None)), x, ws
        )
        with jax.named_scope("areal.loss"):
            return jnp.mean(jnp.square(y.astype(jnp.float32)))

    @jax.jit
    def toy_train_step(ws, x):
        value, grads = jax.value_and_grad(loss)(ws, x)
        with jax.named_scope("areal.optimizer"):
            ws = jax.tree.map(
                lambda w, g: (w - 0.01 * g.astype(jnp.float32)).astype(w.dtype),
                ws, grads,
            )
        return ws, value

    key = jax.random.PRNGKey(0)
    ws = {
        n: 0.05 * jax.random.normal(k, (3, 256, 256), jnp.bfloat16)
        for n, k in zip("agd", jax.random.split(key, 3))
    }
    x = jax.random.normal(key, (256, 256), jnp.bfloat16)
    return toy_train_step, (ws, x)


def record(trace_dir: str) -> str:
    """Run the toy step three times under the profiler; the xplane's path."""
    import jax

    from benchmark.lib.trace_reduce import find_xplane

    step, (ws, x) = toy_step()
    ws, value = step(ws, x)  # compiled outside the session
    jax.block_until_ready(value)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    for _ in range(3):
        ws, value = step(ws, x)
        jax.block_until_ready(value)
    jax.profiler.stop_trace()
    return find_xplane(trace_dir)


# -- the reduction --------------------------------------------------------------

NEW_READERS = {
    "mlp_time_share", "head_time_share", "device_unscoped_share.rollout",
    "device_unscoped_share.train", "train_loss_time_share",
    "train_remat_time_share", "train_optimizer_time_share",
}


@pytest.fixture(scope="module")
def rr():
    from benchmark.lib import region_reduce

    return region_reduce


@pytest.mark.parametrize(
    "tf_op, region, pas",
    [
        (None, "no_op_name", "forward"),
        ("", "no_op_name", "forward"),
        ("jit(small_step)/while/body/closed_call/dot_general:", "unscoped",
         "forward"),
        ("jit(f)/jvp()/while/body/closed_call/areal.mlp/dot_general:",
         "areal.mlp", "forward"),
        ("jit(f)/jvp(areal.loss)/reduce_sum:", "areal.loss", "forward"),
        ("jit(f)/transpose(jvp(areal.loss))/mul:", "areal.loss", "backward"),
        ("jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/"
         "areal.mlp/dot_general:", "areal.mlp", "backward"),
        ("jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/"
         "rematted_computation/areal.attn/tanh:", "areal.attn",
         "rematted_computation"),
        # the innermost region wins; a kernel lies in its caller's
        ("jit(f)/areal.mlp/areal.moe.route/top_k:", "areal.moe.route",
         "forward"),
        ("jit(d)/while/body/areal.attn/jit(paged_flash_attention)/"
         "paged_attn_decode/pallas_call:", "areal.attn", "forward"),
        ("jit(f)/areal.optimizer/sub:", "areal.optimizer", "forward"),
    ],
)
def test_region_and_pass_are_read_from_the_scope_path(rr, tf_op, region, pas):
    assert rr.region_of(tf_op) == region
    assert rr.pass_of(tf_op) == pas


def test_self_time_an_event_at_a_time(rr):
    ev = [(0, 10, "while"), (1, 4, "a"), (4, 5, "b"), (12, 13, "c")]
    got = rr._self_time(ev)
    assert sorted(got) == [
        ("a", 3, True), ("b", 1, True), ("c", 1, True), ("while", 6, False),
    ]


def test_selection_by_region_pass_and_program(rr):
    t = {"seconds": {
        ("jit_a", "areal.mlp", "forward"): 1.0,
        ("jit_a", "areal.moe.experts", "forward"): 2.0,
        ("jit_a", "areal.mlp", "backward"): 4.0,
        ("jit_b", "areal.mlpx", "forward"): 8.0,
        ("jit_b", "unscoped", "forward"): 16.0,
    }}
    assert rr.seconds_of(t) == 31.0
    assert rr.seconds_of(t, regions=("areal.mlp", "areal.moe")) == 7.0
    assert rr.seconds_of(t, regions=("areal.mlp",), passes=("backward",)) == 4.0
    assert rr.seconds_of(t, programs=("jit_b",)) == 24.0
    assert rr.seconds_of(t, regions=rr.UNNAMED) == 16.0
    # most of the time has to lie in a region: a helper from the compile
    # cache does not make a program one that names its regions
    assert not rr.has_regions(t)  # 15 s named, 16 s not
    t["seconds"]["jit_a", "areal.attn", "forward"] = 2.0
    assert rr.has_regions(t)
    assert not rr.has_regions({"seconds": {("p", "unscoped", "forward"): 1.0}})


def test_the_old_fixture_has_no_region_at_all(rr):
    """``small_v5e.xplane.pb`` was recorded before the regions: every
    operation falls into one of the two unnamed bins, which sum to the
    ``XLA Ops`` self time, and a reader says nothing of such a run."""
    from benchmark.lib import trace_reduce

    t = rr.load(OLD_FIXTURE)
    assert {region for _, region, _ in t["seconds"]} == set(rr.UNNAMED)
    assert {program for program, _, _ in t["seconds"]} == {"jit_small_step"}
    want = trace_reduce.reduce_trace(OLD_FIXTURE, "host")
    assert t["self_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert t["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    by_bin = {
        which: rr.seconds_of(t, regions=(which,)) for which in rr.UNNAMED
    }
    # the matmul fusion carries jax's path; copies and the loop itself none
    assert by_bin["unscoped"] == pytest.approx(2.580e-6, rel=1e-3)
    assert by_bin["no_op_name"] == pytest.approx(
        want["busy_s"] - 2.580e-6, rel=1e-3
    )
    names = {op for (_, op) in t["unnamed"]}
    assert {"copy.11", "while", "convolution_tanh_fusion.2"} <= names
    assert t["unnamed"][("unscoped", "convolution_tanh_fusion.2")][1] == (
        "jit(small_step)/while/body/closed_call/dot_general:"
    )
    assert not rr.has_regions(t)


#: picoseconds by (region, pass), each the sum of its instructions' raw
#: ``duration_ps`` over the three calls, read off the fixture's events
#: with the generated protobuf classes (``fusion.96`` = the forward MLP
#: products, 9 executions, 8,873,672 ps; ...): every one a leaf, so self
#: time is duration
RECORDED_PS = {
    ("areal.mlp", "forward"): 8_873_672,  # fusion.96
    ("areal.attn", "forward"): 7_912_032,  # fusion.95
    # bitcast_dynamic-update-slice_fusion.11 / .10, fusion.113,
    # convolution_add_fusion.4: a weight's gradient fused into its
    # stacking keeps the product's path
    ("areal.mlp", "backward"): 4_075_938 + 3_534_844 + 2_738_360 + 2_732_266,
    # bitcast_dynamic-update-slice_fusion.9, convolution_add_fusion.5
    ("areal.attn", "backward"): 3_396_328 + 2_727_734,
    ("areal.attn", "rematted_computation"): 2_587_110,  # fusion.111
    ("areal.mlp", "rematted_computation"): 2_532_344,  # fusion.112
    # subtract_convert_fusion / .1 / .2: the three weights' updates
    ("areal.optimizer", "forward"): 2_751_172 + 2_753_594 + 2_752_578,
    ("areal.loss", "forward"): 1_235_078,  # fusion.47
    # lax.scan's own slices of the stacked weights, backward: four
    # dynamic-slice_bitcast_fusions; and its stacking of the carry forward
    ("unscoped", "backward"): 301_250 + 298_828 + 282_656 + 249_688,
    ("unscoped", "forward"): 235_000,
}
#: the instructions without any ``tf_op``: four ``copy-done``s, four
#: ``broadcast``s, four ``copy-start``s; the two ``while``s add what their
#: bodies do not cover
NO_OP_NAME_LEAVES_PS = (
    2_676_094 + 2_379_922 + 1_461_250 + 467_656
    + 208_828 + 208_750 + 207_500 + 205_938
    + 17_578 + 13_750 + 12_734 + 7_578
)


def test_the_recorded_toy_step_reduces_to_known_seconds_by_region_and_pass(rr):
    from benchmark.lib import trace_reduce

    assert os.path.getsize(FIXTURE) < 200_000
    t = rr.load(FIXTURE)
    assert t["chips"] == 1
    assert {p for p, _, _ in t["seconds"]} == {"jit_toy_train_step"}
    got = {(r, p): s for (_, r, p), s in t["seconds"].items()}
    # an event's times come in whole nanoseconds, rounded down: at most
    # 1 ns an event, 36 events a key
    for key, ps in RECORDED_PS.items():
        assert 0.0 <= ps * 1e-12 - got[key] < 36e-9, key
    assert set(got) == set(RECORDED_PS) | {("no_op_name", "forward")}
    loops = got[("no_op_name", "forward")] - NO_OP_NAME_LEAVES_PS * 1e-12
    assert 0.0 < loops < 1.5e-6  # while.4 and while.5 themselves
    # regions and the two unnamed bins are ALL of the device's time
    busy = trace_reduce.reduce_trace(FIXTURE, "host")["busy_s"]
    assert t["busy_s"] == pytest.approx(busy, rel=1e-9)
    assert t["self_s"] == pytest.approx(busy, rel=5e-3)
    assert sum(got.values()) == pytest.approx(t["self_s"], rel=1e-12)
    # all three passes of both halves, and the two regions of their own
    for region in ("areal.attn", "areal.mlp"):
        assert {p for r, p in got if r == region} == {
            rr.FORWARD, rr.BACKWARD, rr.REMAT
        }
    assert rr.seconds_of(t, passes=(rr.REMAT,)) == pytest.approx(
        (2_587_110 + 2_532_344) * 1e-12, rel=2e-3
    )
    # the largest unnamed operations are named, with their paths
    tb = rr.table(t)
    assert tb["largest_unnamed"]["no_op_name"][0][0] == "copy-done.1"
    assert tb["largest_unnamed"]["unscoped"][0][2] == (
        "jit(toy_train_step)/transpose(jvp())/while/body/squeeze:"
    )
    assert len(tb["largest_unnamed"]["unscoped"]) == 5
    # the compiler's counts: a 256 x 256 x 256 product is 33.6 MFLOP;
    # fusion.96 holds two of them and runs nine times
    assert tb["compiler_flops"]["areal.mlp"] > 9 * 2 * 2 * 256 ** 3
    assert tb["compiler_bytes"]["areal.optimizer"] > 0
    text = rr.format_table(t)
    assert "areal.optimizer" in text and "rematted" not in text.split("\n")[0]


def test_whole_nanoseconds_keep_adjacent_operations_apart(rr):
    """In float seconds ``start + duration`` of one operation rounds above
    the start of the next one time in forty, the next then looks nested in
    it, and the ``while`` around both keeps its time: 2.5% of busy time in
    the train cell's slice (my chip run, PR 38)."""
    ev = [(0, 42_008_338, "while"), (42_001_512, 42_008_338, "fusion.448"),
          (42_008_338, 42_008_340, "copy-start.47")]
    got = dict((n, s) for n, s, _ in rr._self_time(ev))
    assert got == {
        "while": 42_001_512, "fusion.448": 6_826, "copy-start.47": 2,
    }
    assert sum(got.values()) == 42_008_340


_WITH_PB2 = """
import json, sys
try:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
except Exception:
    sys.exit(3)
STATS = set(sys.argv[1].split(","))
out = {}
for path in sys.argv[2:]:
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    planes = out[path] = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        names = {i: m.name for i, m in plane.stat_metadata.items()}
        ops = planes[plane.name] = {}
        for m in plane.event_metadata.values():
            found = ops[m.name] = {}
            for st in m.stats:
                key = names.get(st.metadata_id)
                if key not in STATS:
                    continue
                kind = st.WhichOneof("value")
                value = getattr(st, kind)
                if kind == "ref_value":
                    value = names.get(value, "")
                elif kind == "bytes_value":
                    value = value.decode()
                found[key] = value
print(json.dumps(out))
"""


def test_the_hand_reader_agrees_with_the_generated_protobuf_classes(rr):
    """The wire reader against ``xplane_pb2`` (tensorflow's, where this
    image has it; in a process of its own: the import takes 15 s and a
    thread pool, and nothing else under ``benchmark/`` or ``areal_tpu/``
    imports tensorflow)."""
    import subprocess

    paths = [OLD_FIXTURE, FIXTURE]
    proc = subprocess.run(
        [sys.executable, "-c", _WITH_PB2, ",".join(rr.STATS), *paths],
        capture_output=True, text=True, timeout=300,
    )
    if proc.returncode == 3:
        pytest.skip("no tensorflow.tsl.profiler.protobuf.xplane_pb2 here")
    assert proc.returncode == 0, proc.stderr[-2000:]
    want = json.loads(proc.stdout.splitlines()[-1])
    for path in paths:
        got = rr.read_op_metadata(path)
        assert got == want[path], path
        assert sum(len(v) for v in got.values()) >= 15


def test_every_new_reader_says_nothing_without_a_trace(rr, run, spec):
    """The made-up context of ``test_layer_metric_readers_on_a_made_up_run``
    has no ``work_dir``; neither has a run without ``--trace 1`` a trace."""
    listed = {m["name"] for m in spec["per_layer"]}
    assert NEW_READERS <= listed
    ctx = types.SimpleNamespace(trace={"busy_s": 2.0}, window={})
    for name in sorted(NEW_READERS):
        assert run.load_reader(name).value(ctx) is None, name
    ctx.work_dir = "/nonexistent/out/work"
    for name in sorted(NEW_READERS):
        assert run.load_reader(name).value(ctx) is None, name
    assert rr.share(ctx, regions=("areal.mlp",)) is None


def test_a_reader_reads_the_traced_slice_beside_the_work_directory(
    rr, run, tmp_path, capsys
):
    """``<out>/work`` -> ``<out>/trace/plugins/profile/<run>/*.xplane.pb``,
    as ``run.py`` lays them out; one ``device_by_region`` line a run; a
    program from before the regions reads as nothing."""
    prof = tmp_path / "trace" / "plugins" / "profile" / "r1"
    prof.mkdir(parents=True)
    shutil.copy(FIXTURE, prof / "p.xplane.pb")
    ctx = types.SimpleNamespace(
        work_dir=str(tmp_path / "work"),
        trace={"busy_s": rr.load(str(prof / "p.xplane.pb"))["busy_s"]},
    )
    got = {n: run.load_reader(n).value(ctx) for n in sorted(NEW_READERS)}
    assert all(v is not None and 0.0 <= v <= 100.0 for v in got.values()), got
    assert got["device_unscoped_share.train"] == got["device_unscoped_share.rollout"]
    lines = [
        json.loads(ln) for ln in capsys.readouterr().out.splitlines()
        if ln.startswith("{")
    ]
    assert [ln["event"] for ln in lines] == ["device_by_region"]
    assert set(lines[0]["seconds"]) == {"jit_toy_train_step"}
    old = tmp_path / "old" / "trace" / "plugins" / "profile" / "r1"
    old.mkdir(parents=True)
    shutil.copy(OLD_FIXTURE, old / "p.xplane.pb")
    ctx = types.SimpleNamespace(
        work_dir=str(tmp_path / "old" / "work"), trace={"busy_s": 6.6e-6}
    )
    for name in sorted(NEW_READERS):
        assert run.load_reader(name).value(ctx) is None, name


def test_nothing_under_the_benchmark_or_the_program_imports_tensorflow():
    rx = re.compile(r"^\s*(import|from)\s+tensorflow", re.M)
    found = []
    for top in ("benchmark", "areal_tpu"):
        for dirpath, _, files in os.walk(os.path.join(ROOT, top)):
            if os.sep + "out" + os.sep in dirpath + os.sep:
                continue
            for f in files:
                path = os.path.join(dirpath, f)
                if f.endswith(".py") and os.path.abspath(path) != os.path.abspath(__file__):
                    with open(path) as fh:
                        if rx.search(fh.read()):
                            found.append(path)
    assert not found, found


if __name__ == "__main__":
    out = sys.argv[1]
    os.makedirs(out, exist_ok=True)
    found = record(os.path.join(out, "trace"))
    shutil.copy(found, os.path.join(out, "small_regions_v5e.xplane.pb"))
    from benchmark.lib import region_reduce

    print(region_reduce.format_table(region_reduce.load(found)))
    os._exit(0)
