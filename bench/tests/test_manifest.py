"""The manifest and the files it names: every cell resolves, every name and
unit keeps to the allowed characters, and a new cell is found by name."""
import json
import shutil

import pytest

from bench import manifest


@pytest.fixture(scope="module")
def man():
    return manifest.load()


def test_manifest_is_valid(man):
    assert manifest.validate(man) == []
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}


@pytest.mark.parametrize("cell", ["flight-exact", "flight-svi-stream",
                                  "flight-exact-4chip"])
def test_cell_resolves(man, cell):
    c = manifest.cell(man, cell)
    assert manifest.runner(c["mix"]["kind"]).METRIC in {
        m["name"] for m in c["end_to_end"]}
    assert {m["name"] for m in c["end_to_end"]} >= {"setup_s"}
    assert c["per_layer"]
    for m in c["per_layer"]:
        assert callable(manifest.reader(m["name"]).read)
    assert set(c["limits"]) == {"loss_rel", "grad1_norm", "change_norm"}


def test_held_out_cell_resolves_when_put_back(man):
    """usps-gplvm-exact is held out of BENCHMARK.json; its entries, kept
    in its workload file, make a valid manifest again."""
    from bench.tests import _tiny

    back = _tiny.with_held_out(man, "usps-gplvm-exact")
    assert manifest.validate(back) == []
    c = manifest.cell(back, "usps-gplvm-exact")
    assert {m["name"] for m in c["per_layer"]} >= {"psi2_roofline.exact"}


def test_config_files_hold_their_sizes(man):
    for entry in man["configs"]:
        cfg = json.loads((manifest.ROOT / entry["file"]).read_text())
        assert cfg["name"] == entry["name"]
        assert cfg["reduced"] == entry["reduced"]
        assert all(isinstance(cfg[k], int) for k in ("n", "m", "q", "d"))


@pytest.mark.parametrize("bad", ["a b", "a/b", "a,b", "", "-x", "x" * 65,
                                 "naïve"])
def test_bad_names_are_refused(man, bad):
    broken = json.loads(json.dumps(man))
    broken["workloads"][0]["name"] = bad
    assert any("bad name" in e for e in manifest.validate(broken))


def test_bad_unit_is_refused(man):
    broken = json.loads(json.dumps(man))
    broken["end_to_end"][0]["unit"] = "rows per s"
    assert any("bad unit" in e for e in manifest.validate(broken))


def test_new_cell_is_found_without_any_edit(man, tmp_path):
    """A cell added as a mix file, a limits file and a manifest entry, in a
    copy of the benchmark, resolves by name with no other file changed."""
    root = tmp_path / "repo"
    shutil.copytree(manifest.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    extra = json.loads(json.dumps(man))
    extra["workloads"].append({
        "name": "flight-exact-2chip", "config": "flight-m100",
        "traffic": "exact-2chip", "chips": 4, "why": "test"})
    for m in extra["end_to_end"] + extra["per_layer"]:
        if "flight-exact" in m.get("workloads", []):
            m["workloads"].append("flight-exact-2chip")
    (root / "BENCHMARK.json").write_text(json.dumps(extra))
    (root / "bench" / "traffic" / "exact-2chip.json").write_text(json.dumps(
        {"kind": "exact", "mesh": 2, "reduce_mode": "serial"}))
    (root / "bench" / "workloads" / "flight-exact-2chip.json").write_text(
        json.dumps({"limits": {"loss_rel": 1.0}}))
    c = manifest.cell(manifest.load(root), "flight-exact-2chip", root)
    assert c["mix"]["mesh"] == 2 and c["config"]["n"] == 2_000_000
    assert "reg_stats_roofline.exact" in {m["name"] for m in c["per_layer"]}
    assert manifest.validate(manifest.load(root), root) == []


def test_judge_fails_and_nulls_non_finite_readings():
    from bench import compare

    ok, checks = compare.judge({"loss_rel": float("nan"), "grad1_norm": 0.5},
                               {"loss_rel": 1.0, "grad1_norm": 1.0,
                                "change_norm": 1.0})
    assert not ok
    assert checks["loss_rel"]["value"] is None
    assert checks["change_norm"]["value"] is None
    assert compare.judge({"loss_rel": 0.5}, {"loss_rel": 1.0})[0]
