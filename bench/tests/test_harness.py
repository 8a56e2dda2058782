"""The harness finds everything by name from data, and speaks its contract."""

import json
import math
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import harness
from bench.traffic import serve_arrivals
from conftest import ROOT

BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


@pytest.mark.parametrize("name", [w["name"] for w in BENCHMARK["workloads"]])
def test_every_cell_resolves_from_data(name):
    cell = harness.load_cell(name)
    assert cell.config["name"] == cell.config_name
    assert harness.driver_module(cell).run
    assert harness.reference_module(cell).served_token_gaps
    assert harness.family_module(cell).train_step_flops
    assert cell.end_to_end and cell.per_layer
    assert "setup_s" in [m["name"] for m in cell.end_to_end]
    for m in cell.per_layer:
        assert callable(harness.metric_reader(m["name"]).read)


def test_every_metric_has_a_reader_and_moves_a_reported_metric():
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    for m in BENCHMARK["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics", m["name"] + ".py"))
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert "workloads" not in moved or cell in moved["workloads"]


@pytest.mark.parametrize("name", [w["name"] for w in BENCHMARK["workloads"]])
def test_every_cell_holds_its_metrics_to_bounds(name):
    """Each end-to-end metric a cell reports has a bound within the cap, and
    each of its per-layer metrics moves one of them other than the set-up."""
    cell = harness.load_cell(name)
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert all(0.01 <= m["bound"] <= 0.25 for m in cell.end_to_end)
    assert all(m["moves"] in reported - {"setup_s"} for m in cell.per_layer)


def test_a_cell_added_as_files_alone_is_found(tmp_path):
    """A later PR adds a configuration, a traffic mix, limits and a metric as
    new files plus entries in BENCHMARK.json, and edits no existing file."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    b = json.load(open(tmp_path / "BENCHMARK.json"))
    conf = json.load(open(tmp_path / "bench/configs/glm4-9b-serve.json"))
    conf["name"] = "fixture-model"
    json.dump(conf, open(tmp_path / "bench/configs/fixture-model.json", "w"))
    traffic = json.load(open(tmp_path / "bench/traffic/chat.json"))
    traffic["rate_rps"] = 1.0
    json.dump(traffic, open(tmp_path / "bench/traffic/fixture-mix.json", "w"))
    json.dump({"token_gap": 1.0}, open(tmp_path / "bench/limits/fixture.cell.json", "w"))
    (tmp_path / "bench/metrics/fixture_metric.py").write_text(
        "def read(outcome):\n    return 42.0\n")
    b["configs"].append({"name": "fixture-model", "source": "https://example.org/fixture",
                         "file": "bench/configs/fixture-model.json", "reduced": [], "why": "x"})
    b["workloads"].append({"name": "fixture.cell", "config": "fixture-model",
                           "traffic": "fixture-mix", "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "fixture_metric", "unit": "%", "better": "higher",
                           "source": "program_span", "layer": "serving engine",
                           "moves": "ttft_p95_ms", "workloads": ["fixture.cell"]})
    b["end_to_end"] += [{"name": name, "unit": "ms", "better": "lower", "bound": 0.2,
                         "source": "host_clock", "workloads": ["fixture.cell"]}
                        for name in ("ttft_p95_ms", "tpot_p95_ms")]
    json.dump(b, open(tmp_path / "BENCHMARK.json", "w"))

    cell = harness.load_cell("fixture.cell", root=tmp_path)
    assert cell.config["name"] == "fixture-model"
    assert cell.traffic["rate_rps"] == 1.0
    assert cell.limits == {"token_gap": 1.0}
    assert [m["name"] for m in cell.per_layer] == ["fixture_metric"]
    assert {m["name"] for m in cell.end_to_end} == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert harness.driver_module(cell, root=tmp_path).run
    assert harness.metric_reader("fixture_metric", root=tmp_path).read(None) == 42.0


def test_a_family_that_does_not_exist_fails_with_its_path(tmp_path):
    """A configuration names its block family; the harness looks for
    ``bench/families/<family>.py`` and says where it looked."""
    cell = harness.draft_cell("train.glm4-9b.4k", "glm4-9b-train", "pretrain-4k")
    cell.config = dict(cell.config, reference="no_such_block")
    with pytest.raises(FileNotFoundError) as e:
        harness.family_module(cell, root=tmp_path)
    assert str(tmp_path / "bench" / "families" / "no_such_block.py") in str(e.value)
    with pytest.raises(FileNotFoundError, match="families/no_such_block.py"):
        harness.family_module(cell)


#: a family of three block kinds and a tail, as a later configuration adds it:
#: it names each group of the program's stack (``scan/<i>``, ``tail/<i>``)
TWO_KINDS_FAMILY = '''
import jax

GROUPS, TAIL = ("recurrent", "local_a", "local_b"), ("last",)


def _named(prefix, tree):
    return {prefix + ".".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def reference_leaves(tree):
    out = _named("", {k: v for k, v in tree.items() if k not in ("scan", "tail")})
    for group, sub in zip(GROUPS, tree["scan"], strict=True):
        out.update(_named(group + ".", sub))
    for group, sub in zip(TAIL, tree["tail"], strict=True):
        out.update(_named(group + ".", sub))
    return out
'''


def test_a_family_of_two_block_kinds_keys_the_train_norms_by_its_leaf_names(tmp_path):
    """A family written as a new file maps a stack of several block kinds with
    a tail onto its reference's leaf names, and the train driver's gradient
    and weight-change norms come out keyed by those names, one per leaf of
    the program's tree; the drivers name no family."""
    import types

    import jax
    import jax.numpy as jnp

    from bench.drivers import serve, train
    from bench.reference import dense_gqa
    from repro.configs import get_smoke_config
    from repro.models import model as M

    (tmp_path / "bench" / "families").mkdir(parents=True)
    (tmp_path / "bench" / "families" / "two_kinds.py").write_text(TWO_KINDS_FAMILY)
    cell = harness.draft_cell("train.glm4-9b.4k", "glm4-9b-train", "pretrain-4k")
    cell.config = dict(cell.config, reference="two_kinds")
    F = harness.family_module(cell, root=tmp_path)

    cfg = get_smoke_config("recurrentgemma-9b")      # rglru, attn_local x 2; 4 layers
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    assert len(params["scan"]) == 3 and len(params["tail"]) == 1
    by_name = F.reference_leaves(params)
    assert len(by_name) == len(jax.tree.leaves(params))
    assert {k.split(".")[0] for k in by_name} >= {"recurrent", "local_a", "local_b", "last"}

    def norm(x):
        return float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))

    grads = train.named_norms(F, jax.tree.map(norm, params))
    assert grads == {k: norm(x) for k, x in by_name.items()}
    assert grads["local_a.wq"] != grads["local_b.wq"]

    master = jax.tree.map(lambda x: x.astype(jnp.float32) * 3, params)
    R = types.SimpleNamespace(diff_norm=dense_gqa.diff_norm,
                              stacked_leaf=lambda a, seed, k: by_name[k].astype(jnp.float32))
    change = train.change_norms(F, R, None, 0, master)
    assert sorted(change) == sorted(by_name)
    for k, x in by_name.items():
        assert change[k] == pytest.approx(2 * norm(x), rel=1e-5), k

    for driver in (train, serve):
        source = open(driver.__file__).read()
        assert "dense_gqa" not in source and "bench.families" not in source


def test_serve_traffic_is_a_function_of_the_seed():
    spec = json.load(open(os.path.join(ROOT, "bench/traffic/chat.json")))
    segments = [5.0, 40.0, 10.0]
    a = serve_arrivals(spec, 2**33 + 7, segments, 151552)
    b = serve_arrivals(spec, 2**33 + 7, segments, 151552)
    c = serve_arrivals(spec, 2**33 + 8, segments, 151552)
    assert [(x.at, x.prompt, x.max_new) for x in a] == [(x.at, x.prompt, x.max_new) for x in b]
    assert [x.prompt for x in a] != [x.prompt for x in c]
    assert len(a) == sum(math.ceil(s * spec["rate_rps"]) for s in segments)
    # another seed offers the window the same work in another order
    window = lambda xs: [x for x in xs if 5.0 <= x.at < 45.0]  # noqa: E731
    wa, wc = window(a), window(c)
    assert len(wa) == len(wc) == math.ceil(40.0 * spec["rate_rps"])
    assert [x.at for x in wa] != [x.at for x in wc]
    assert sorted(len(x.prompt) for x in wa) == sorted(len(x.prompt) for x in wc)
    assert sorted(x.max_new for x in wa) == sorted(x.max_new for x in wc)
    gaps = lambda xs: np.diff([x.at for x in xs] + [45.0])  # noqa: E731
    np.testing.assert_allclose(sorted(gaps(wa)), sorted(gaps(wc)), rtol=1e-9)
    assert {len(x.prompt) for x in a} <= {256, 512, 1024, 2048}
    assert all(32 <= x.max_new <= 512 for x in a)


def test_train_traffic_is_a_function_of_the_seed():
    from bench.drivers.train import tokens_at

    a, _ = tokens_at(2**33 + 7, 0, 1, 4096, 18944)
    b, _ = tokens_at(2**33 + 7, 0, 1, 4096, 18944)
    c, _ = tokens_at(2**33 + 8, 0, 1, 4096, 18944)
    d, _ = tokens_at(2**33 + 7, 1, 1, 4096, 18944)
    assert (a == b).all() and not (a == c).all() and not (a == d).all()


def test_jax_seed_takes_large_seeds():
    seeds = {harness.jax_seed(s) for s in (0, 1, 2**31 + 5, 2**40 + 1)}
    assert len(seeds) == 4 and all(0 <= s < 2**31 for s in seeds)


@pytest.mark.parametrize("which", ["train", "serve"])
def test_result_line_has_the_contract_keys(which, tiny_train, tiny_serve, v5e, capsys):
    cell = tiny_train if which == "train" else tiny_serve
    result = harness.run_cell(cell.name, 2**31 + 11, 1.0, False, require_chip=False,
                              cell=cell, peak=v5e)
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert result["correct"] is True and result["attempted"] > 0
    harness.emit(result)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == json.loads(json.dumps(result))
    assert err.strip().splitlines()[-1].startswith("check ")


def test_a_traced_serve_run_reads_every_serve_metric_but_the_chips(tiny_serve, v5e, tmp_path,
                                                                     monkeypatch):
    """Off the chip there is no device plane, so only the device's idle share
    is left out; every other reader finds its number.  The profiler covers the
    window's last ``trace_seconds``, so that the seconds its stop holds the
    loop fall after the close."""
    import time

    marks = {}

    def spy(name, real):
        def call(*args, **kwargs):
            marks[name] = time.monotonic()
            return real(*args, **kwargs)
        return call

    monkeypatch.setattr(harness, "CACHE_DIR", tmp_path)
    monkeypatch.setattr(harness.Tracer, "start", spy("start", harness.Tracer.start))
    monkeypatch.setattr(harness.Tracer, "stop", spy("stop", harness.Tracer.stop))
    driver = harness.driver_module(tiny_serve)
    drive = driver.drive
    monkeypatch.setattr(driver, "drive", lambda *a: marks.setdefault("rec", drive(*a)))
    tiny_serve.traffic["trace_seconds"] = 0.3
    result = harness.run_cell(tiny_serve.name, 2**31 + 17, 1.5, True, require_chip=False,
                              cell=tiny_serve, peak=v5e)
    assert set(result["metrics"]) == {m["name"] for m in tiny_serve.per_layer} - {
        "device_idle_share.serve"}
    assert all(math.isfinite(m["value"]) for m in result["metrics"].values())
    close = marks["rec"]["t_close"]
    assert close - 0.35 <= marks["start"] < marks["stop"] and marks["stop"] >= close


@pytest.mark.parametrize("which", ["train", "serve"])
def test_reference_runs_once_the_program_is_gone(which, tiny_train, tiny_serve, v5e,
                                                 monkeypatch):
    """At the cells' size the program's state and the reference do not fit on
    one chip together: no array the run made may be alive when it starts."""
    import gc

    import jax

    from bench.reference import dense_gqa as R

    cell = tiny_train if which == "train" else tiny_serve
    name = "train_reference" if which == "train" else "served_token_gaps"
    before = {id(x) for x in jax.live_arrays()}
    alive = []

    def spy(*args, **kwargs):
        gc.collect()
        alive.append([x.shape for x in jax.live_arrays() if id(x) not in before])
        return getattr(R, "_" + name)(*args, **kwargs)

    monkeypatch.setattr(R, "_" + name, getattr(R, name), raising=False)
    monkeypatch.setattr(R, name, spy)
    harness.run_cell(cell.name, 2**31 + 13, 1.0, False, require_chip=False, cell=cell, peak=v5e)
    assert alive == [[]]


def test_command_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "train.glm4-9b.4k", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr
