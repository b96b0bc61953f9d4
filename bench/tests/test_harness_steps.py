"""The seams a later cell plugs into: step modules, data generators, the check by steps.

Each step of a closed loop's visit is a file, ``bench/steps/<step>.py``,
and each configuration's data a generator, ``bench/data/<generator>.py``,
both found by name (:mod:`bench.cells`). The closed loop's check walks a
visit's steps and sums what each step's ``reference`` counts.
"""

import json
import shutil

import numpy as np
import pytest

import bench.run as brun
from bench import cells, check
from bench.cells import ROOT
from bench.data import eeg
from bench.labels import derived_seeds
from bench.reference import refit
from bench.reference.draws import permutation_indices

PERM, GROUP = "eeg_binary_p3800.perm1k", "eeg_3class_p1900.group16"


def _served(tiny_cell, name):
    cell = tiny_cell(name, subjects=1)
    subj = brun.make_subjects(cell, 2**31 + 21)[0]
    subj.x64 = np.asarray(subj.x, np.float64)
    engine = brun.make_engine(cell)
    from repro.serve import Client

    return cell, subj, engine, Client(engine)


def test_multiclass_visit_steps_run_and_check(tiny_cell):
    cell, subj, engine, client = _served(tiny_cell, GROUP)
    steps, traffic = cell.steps, cell.traffic
    assert list(steps) == ["register", "cv", "permutation", "release"]
    assert not hasattr(steps["register"], "reference")
    assert not hasattr(steps["release"], "reference")
    assert steps["register"].run(client, engine, subj, {"step": "register"}, 1) == {}
    assert subj.handle is not None
    prod = steps["cv"].run(client, engine, subj, {"step": "cv"}, 1)
    assert set(prod) == {"cv", "labels"} and prod["labels"] == 1
    assert prod["cv"].shape == subj.te.shape
    perm = steps["permutation"].run(client, engine, subj, {"step": "permutation", "n_perm": 40}, 9)
    assert set(perm) == {"null", "observed", "n_perm", "labels"}
    assert perm["null"].shape == (40,) and perm["labels"] == 41 and perm["n_perm"] == 40
    assert steps["release"].run(client, engine, subj, {"step": "release"}, 1) == {}
    assert subj.handle is None
    prod.update(perm, seed=9)

    rng = np.random.default_rng(0)
    (count, per), = steps["cv"].reference(subj, prod, rng, traffic, None).values()
    assert per == 1 and 0 <= count <= subj.te.size // 20
    moved = dict(prod, cv=(prod["cv"] + 1) % subj.num_classes)  # every prediction moved
    (count, _), = steps["cv"].reference(subj, moved, rng, traffic, None).values()
    assert count >= subj.te.size * 0.9

    ref = steps["permutation"].reference
    (count, per), = ref(subj, prod, np.random.default_rng(0), traffic, None).values()
    assert per == 41 and 0 <= count <= per * subj.te.size // 20
    flipped = dict(prod, null=1.0 - prod["null"], observed=1.0 - prod["observed"])
    (bad, _), = ref(subj, flipped, np.random.default_rng(0), traffic, None).values()
    assert bad > 10 * max(count, 1)
    (ctl, per_c), = ref(subj, prod, np.random.default_rng(0), traffic, refit.ONE_PASS).values()
    assert per_c == per and ctl >= 0


def test_binary_permutation_step_samples_its_draws(tiny_cell):
    cell, subj, engine, client = _served(tiny_cell, PERM)
    steps = cell.steps
    assert list(steps) == ["register", "permutation"]
    steps["register"].run(client, engine, subj, {"step": "register"}, 1)
    step = {"step": "permutation", "n_perm": 300}
    prod = dict(steps["permutation"].run(client, engine, subj, step, 5), seed=5)
    assert prod["labels"] == 301 and isinstance(prod["observed"], float)
    traffic = dict(cell.traffic, check_draws=7)
    (count, per), = steps["permutation"].reference(
        subj, prod, np.random.default_rng(3), traffic, None).values()
    assert per == 8 and count <= 8 * subj.te.size // 20
    # the draws compared are those the check's generator samples, regenerated from the seed
    draws = check.sample(np.random.default_rng(3), 300, 7)
    perms = permutation_indices(5, len(subj.y), 300)[draws]
    acc = check.accuracy(subj, subj.y[perms], refit.FLOAT64)
    assert np.abs(acc - prod["null"][draws]).max() * subj.te.size <= count + 0.5


def _copy_tree(tmp_path):
    root = tmp_path / "tree"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    return root


@pytest.mark.parametrize("kind", ["step", "generator"])
def test_unknown_step_or_generator_is_refused_with_its_path(tmp_path, kind):
    root = _copy_tree(tmp_path)
    if kind == "step":
        path = root / "bench" / "traffic" / "perm1k.json"
        doc = json.loads(path.read_text())
        doc["visit"].append({"step": "no_such_step"})
        missing = root / "bench" / "steps" / "no_such_step.py"
    else:
        path = root / "bench" / "configs" / "eeg_binary_p3800.json"
        doc = json.loads(path.read_text())
        doc["data"]["generator"] = "no_such_generator"
        missing = root / "bench" / "data" / "no_such_generator.py"
    path.write_text(json.dumps(doc))
    with pytest.raises(FileNotFoundError, match=str(missing)):
        cells.resolve(PERM, root)


def test_a_configuration_names_its_generator(tmp_path):
    root = _copy_tree(tmp_path)
    path = root / "bench" / "configs" / "eeg_binary_p3800.json"
    doc = json.loads(path.read_text())
    del doc["data"]["generator"]
    path.write_text(json.dumps(doc))
    with pytest.raises(KeyError, match="generator"):
        cells.resolve(PERM, root)


@pytest.mark.parametrize("name", [PERM, GROUP])
def test_subjects_through_the_generator_lookup_are_the_eeg_simulators(tiny_cell, name):
    cell = tiny_cell(name)
    cfg = cell.config
    got = brun.make_subjects(cell, 2**32 + 7)
    seeds = derived_seeds(2**32 + 7, 3, int(cell.traffic["subjects"]))
    assert len(got) == len(seeds)
    for subj, s in zip(got, seeds):
        x, y = eeg.simulate(int(s), cfg["data"], int(cfg["num_classes"]))
        te, tr = brun.kfold(int(x.shape[0]), int(cfg["folds"]), int(s))
        np.testing.assert_array_equal(np.asarray(subj.x), np.asarray(x))
        np.testing.assert_array_equal(subj.te, te)
        np.testing.assert_array_equal(subj.tr, tr)
        assert subj.x.shape == (120, eeg.n_features(cfg["data"]))


def _parents_check_closed(cell, subjects, products, seed, control=None):
    """The closed-loop check as it was before steps had references: the oracle."""
    def sample(rng, count, k):
        return np.sort(rng.choice(count, min(k, count), replace=False))

    def answers(subj, labels, prec):
        x, te, tr, lam = subj.x64, subj.te, subj.tr, subj.lam
        if subj.num_classes == 2:
            dv = refit.binary_dvals(x, labels.T, te, tr, lam, prec)
            return refit.fold_accuracy(dv, labels.T[te]), None
        preds, _ = refit.multiclass_predict(x, labels, te, tr, lam, subj.num_classes, prec)
        return (preds == labels[:, te]).mean(axis=(1, 2)), preds

    traffic = cell.traffic
    rng = np.random.default_rng([int(seed), 11])
    visits = sample(rng, len(products), int(traffic["check_visits"]))
    trials = vectors = 0
    for v in visits:
        prod = products[v]
        subj = subjects[prod["subject"]]
        y, te = subj.y, subj.te
        cols = [y]
        got = []
        if "null" in prod:
            draws = sample(rng, prod["n_perm"], int(traffic["check_draws"]))
            perms = permutation_indices(prod["seed"], len(y), prod["n_perm"])[draws]
            cols += list(y[perms])
            got = [prod["observed"]] + list(np.asarray(prod["null"])[draws])
        labels = np.stack(cols)
        acc, preds = answers(subj, labels, refit.FLOAT64)
        cv = prod.get("cv")
        if control is not None:
            acc_c, preds_c = answers(subj, labels, control)
            got = list(acc_c[:len(got)])
            cv = None if cv is None else preds_c[0]
        if cv is not None:
            trials += int((np.asarray(cv) != preds[0]).sum())
            vectors += 1
        if got:
            gap = np.abs(np.asarray(got, np.float64) - acc[:len(got)]) * te.size
            trials += float(np.rint(gap).sum())
            vectors += len(got)
    return {"flips_per_1k": 1000.0 * trials / max(vectors, 1)}


def test_check_by_steps_equals_the_parents_formula_on_group16(tiny_cell):
    cell = tiny_cell(GROUP)
    keep = {}
    seed = 2**31 + 29
    res = brun.run_cell(cell, seed, 1.0, False, keep=keep)
    subjects, products = keep["subjects"], keep["products"]
    assert len(products) >= 2
    # the visits the check samples have moved their predictions: make some flips count
    moved = [dict(p, cv=np.where(np.arange(p["cv"].size).reshape(p["cv"].shape) % 7 == 0,
                                 (p["cv"] + 1) % 3, p["cv"]), null=p["null"][::-1].copy())
             for p in products]
    for prods in (products, moved):
        for control in (None, refit.ONE_PASS):
            want = _parents_check_closed(cell, subjects, prods, seed, control)
            assert check.check_closed(cell, subjects, prods, seed, control) == want
    assert res["checks"]["flips_per_1k"]["value"] == _parents_check_closed(
        cell, subjects, products, seed)["flips_per_1k"]
    assert _parents_check_closed(cell, subjects, moved, seed)["flips_per_1k"] > 50
