"""Worker families: the harness finds one by the configuration's
``family`` key, runs it through ``run_cell`` without knowing it, and
reads ``examples_per_s`` from what its ``summary`` counts.

A small TMSN-SGD family lives only among the test files
(``data/families/tmsn_sgd.py``, with its configuration, traffic and
limits beside it) and reaches the harness through the same loader with
another root.
"""

import ast
import json
import os
from types import SimpleNamespace

import pytest

import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH, "tests", "data")
SEED = 3000000017  # more than 31 bits: `--seed` takes any whole number


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def sgd_spec() -> dict:
    cfg = _json(DATA, "configs", "tmsn-sgd-tiny.json")
    return {
        "cell": {"name": "tmsn-sgd-tiny.rounds", "config": cfg["name"], "traffic": "sgd-rounds", "chips": 1},
        "config": cfg,
        "traffic": _json(DATA, "traffic", "sgd-rounds.json"),
        "limits": _json(DATA, "limits", f"{cfg['name']}.json"),
        "family": run.load_family(cfg["family"], root=run.Path(DATA)),
        "end_to_end": [{"name": "examples_per_s", "unit": "examples/s"}, {"name": "setup_s", "unit": "s"}],
        "per_layer": [],
    }


def test_a_family_in_new_files_runs_through_run_cell():
    spec = sgd_spec()
    res = run.run_cell(spec["cell"]["name"], SEED, 0.2, False, spec=spec, require_chip=False)
    json.loads(json.dumps(res))  # a result line
    assert set(res["checks"]) == set(spec["limits"])
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["metrics"]["examples_per_s"]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0


def test_unknown_family_names_the_file_it_looked_for():
    with pytest.raises(SystemExit, match=r"families/no_such_family\.py"):
        run.load_family("no_such_family")
    spec = sgd_spec()
    del spec["family"]
    spec["config"] = dict(spec["config"], family="no_such_family")
    with pytest.raises(SystemExit, match=r"no_such_family"):
        run.run_cell("x", SEED, 0.1, False, spec=spec, require_chip=False)


def test_a_configuration_without_family_is_refused():
    with pytest.raises(SystemExit, match="family"):
        run.load_family(None)


def test_the_harness_names_no_family():
    """``run.py`` imports nothing of a family: a new one needs new files
    only. The test family is not among the harness's own families."""
    with open(os.path.join(BENCH, "run.py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module)
    assert not imported & {"reference", "compare", "splice_data", "control"}, imported
    assert not any(m.startswith(("repro.boosting", "repro.core.sgd_worker")) for m in imported), imported
    assert not os.path.exists(os.path.join(BENCH, "families", "tmsn_sgd.py"))


# ----------------------------------------------------------------------
# the Sparrow reading is the parent's
# ----------------------------------------------------------------------


def _old_examples_per_s(ctx):
    """The reader as it stood before families counted examples."""
    t = ctx["trainings"]
    cfg = ctx["config"]
    rows = min(cfg["scanner"]["chunk_size"], cfg["sparrow"]["sample_size"])
    examples = cfg["engine"]["n_workers"] * rows * sum(x["rounds"] for x in t)
    return examples / sum(x["seconds"] for x in t)


@pytest.mark.parametrize("traffic", ["rounds", "to-target"])
def test_sparrow_examples_per_s_is_the_old_formula(traffic):
    cfg = _json(BENCH, "configs", "splice-w16.json")
    tr = _json(BENCH, "traffic", f"{traffic}.json")
    sparrow = run.load_family(cfg["family"])
    # a window in the shape run_cell records it: 16 workers, trainings of
    # the traffic's rounds, the engines in turn
    rounds = [104] * 7 if traffic == "rounds" else [102] * 8
    seconds = [1.43561, 1.43702, 1.39478, 1.43911, 1.43625, 1.43504, 1.43777, 1.38943][: len(rounds)]
    trainings = []
    for k, (r, s) in enumerate(zip(rounds, seconds)):
        res = SimpleNamespace(rounds=r, final_certificates=[-0.5] * 16, messages_accepted=40)
        trainings.append(dict(sparrow.summary(res, cfg, tr), seconds=s, engine=k % 4))
    w, chunk, sample = 16, cfg["scanner"]["chunk_size"], cfg["sparrow"]["sample_size"]
    assert [t["examples"] for t in trainings] == [w * min(chunk, sample) * r for r in rounds]
    ctx = {"config": cfg, "traffic": tr, "trainings": trainings}
    assert run.load_reader("end_to_end", "examples_per_s")(ctx) == _old_examples_per_s(ctx)
