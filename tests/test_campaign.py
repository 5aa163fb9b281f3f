"""The campaign layer (DESIGN.md §15): content-addressed spec hashing, the
cell registry/DAG, envelope status + resume + force semantics on the tiny
``smoke`` campaign, the legacy-envelope migration pins, and the validate
staleness gate.

The field audit is the load-bearing test: ``spec_hash`` is a cache key, so
a config field it silently ignores means stale results get served as
CURRENT.  Every field of ``ExperimentSpec`` / ``RunConfig`` /
``FleetConfig`` must appear in the flip tables below; adding a field
without triaging it here fails the coverage assert.
"""

import copy
import dataclasses
import json
import os

import numpy as np
import pytest

from repro.config import RunConfig
from repro.experiments import campaign, registry, validate
from repro.experiments.result import SCHEMA_VERSION
from repro.experiments.spec import ExperimentSpec
from repro.experiments.spec_hash import (canonical_echo, content_hash,
                                         spec_hash, spec_hash_from_echo)
from repro.membership import MembershipTimeline
from repro.serve.fleet import FleetConfig
from repro.serve.publication import PublicationPolicy

RESULTS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "results")

# populate the registry before any test monkeypatches _CELLS — the lazy
# loader only ever imports the cells package once
registry._load_cells()


# ---------------------------------------------------------------------------
# spec-hash field audit (every field flips the hash)
# ---------------------------------------------------------------------------
# field -> replace() kwargs that change ONLY that field's meaning.  Where a
# flip needs companion fields to pass __post_init__ validation (e.g.
# spmd_learners needs placement="spmd"), the companions are listed under
# "extra": the test compares base+extra against base+extra+flip so the
# audited field is the only difference.
_RUN_FLIPS = {
    "protocol": {"protocol": "softsync"},
    "n_softsync": {"n_softsync": 2, "extra": {"protocol": "softsync",
                                              "n_learners": 4}},
    "n_learners": {"n_learners": 3},
    "minibatch": {"minibatch": 64},
    "base_lr": {"base_lr": 0.25},
    "ref_batch": {"ref_batch": 256},
    "lr_policy": {"lr_policy": "staleness_inverse"},
    "momentum": {"momentum": 0.8},
    "optimizer": {"optimizer": "adagrad"},
    "weight_decay": {"weight_decay": 0.1},
    "warmstart_epochs": {"warmstart_epochs": 1},
    "seed": {"seed": 7},
    "duration_model": {"duration_model": "two_speed"},
    "slow_fraction": {"slow_fraction": 0.5},
    "slow_factor": {"slow_factor": 8.0},
    "pareto_alpha": {"pareto_alpha": 3.0},
    "pareto_scale": {"pareto_scale": 0.25},
    "shards": {"shards": 2},
    "groups": {"groups": 1},
    "shard_pull_jitter": {"shard_pull_jitter": 0.5},
    "ring_dtype": {"ring_dtype": "bf16"},
    "placement": {"placement": "spmd"},
    "spmd_learners": {"spmd_learners": 1, "extra": {"placement": "spmd"}},
    "membership": {"membership": MembershipTimeline(((1.0, 0, "crash"),))},
    "backup": {"backup": 1, "extra": {"n_learners": 4}},
    "num_microbatches": {"num_microbatches": 2},
    "remat": {"remat": False},
    "fsdp": {"fsdp": True},
    "use_pallas": {"use_pallas": True},
    "attn_impl": {"attn_impl": "naive"},
    "attn_q_chunk": {"attn_q_chunk": 512},
    "attn_kv_chunk": {"attn_kv_chunk": 512},
    "unroll": {"unroll": True},
    "residual_spec": {"residual_spec": ("data", None)},
    "serving": {"serving": FleetConfig()},
}

_FLEET_FLIPS = {
    "replicas": {"replicas": 3},
    "policy": {"policy": PublicationPolicy(kind="on_demand")},
    "request_rate": {"request_rate": 8.0},
    "request_samples": {"request_samples": 64},
    "diurnal_amplitude": {"diurnal_amplitude": 0.5},
    "diurnal_period": {"diurnal_period": 100.0},
    "service_base_s": {"service_base_s": 0.04},
    "service_per_sample_s": {"service_per_sample_s": 1e-3},
    "publish_cost_s": {"publish_cost_s": 0.1},
    "max_requests": {"max_requests": 1000},
    "membership": {"membership": MembershipTimeline(((1.0, 0, "crash"),))},
}

# ExperimentSpec's own fields; "run" is audited by _RUN_FLIPS.
_SPEC_FLIPS = {
    "run": {"run": RunConfig(seed=99)},
    "problem": {"problem": "mlp_teacher"},
    "problem_args": {"problem_args": (("hidden", 8),),
                     "extra": {"problem": "mlp_teacher"}},
    "steps": {"steps": 200},
    "epochs": {"epochs": 2.0, "steps": None,
               "extra": {"problem": "mlp_teacher", "epochs": 1.0,
                         "steps": None}},
    "duration": {"duration": "calibrated:base:300mb"},
    "eval_every": {"eval_every": 10},
    "engine": {"engine": "measure"},
    "tag": {"tag": "flipped"},
}

_BASE_SPEC = ExperimentSpec(run=RunConfig(), steps=100)


def _flip_hashes(flips, apply):
    """(base_hash, flipped_hash) per field via the flip table."""
    out = {}
    for field, flip in flips.items():
        flip = dict(flip)
        extra = flip.pop("extra", {})
        out[field] = (apply(extra), apply({**extra, **flip}))
    return out


def test_every_runconfig_field_flips_spec_hash():
    def apply(kw):
        return spec_hash(_BASE_SPEC.replace(run=RunConfig(**kw)))
    for field, (h0, h1) in _flip_hashes(_RUN_FLIPS, apply).items():
        assert h0 != h1, f"RunConfig.{field} does not reach spec_hash"


def test_every_fleetconfig_field_flips_spec_hash():
    def apply(kw):
        return spec_hash(_BASE_SPEC.replace(
            run=RunConfig(serving=FleetConfig(**kw))))
    for field, (h0, h1) in _flip_hashes(_FLEET_FLIPS, apply).items():
        assert h0 != h1, f"FleetConfig.{field} does not reach spec_hash"


def test_every_spec_field_flips_spec_hash():
    def apply(kw):
        base = {"run": RunConfig(), "steps": 100}
        base.update(kw)
        return spec_hash(ExperimentSpec(**base))
    for field, (h0, h1) in _flip_hashes(_SPEC_FLIPS, apply).items():
        assert h0 != h1, f"ExperimentSpec.{field} does not reach spec_hash"


@pytest.mark.parametrize("cls,table", [
    (RunConfig, _RUN_FLIPS),
    (FleetConfig, _FLEET_FLIPS),
    (ExperimentSpec, _SPEC_FLIPS),
])
def test_flip_tables_cover_every_field(cls, table):
    # a new config field MUST be triaged here: either give it a flip (it
    # feeds the content address) or consciously exclude it with a comment
    # in this test (it is representation only).  Nothing is excluded today.
    fields = {f.name for f in dataclasses.fields(cls)}
    missing = fields - set(table)
    assert not missing, (
        f"untriaged {cls.__name__} fields {sorted(missing)}: add them to "
        f"the flip table in tests/test_campaign.py (or explicitly exclude "
        f"them here) so spec_hash coverage stays total")
    unknown = set(table) - fields
    assert not unknown, f"flip table names unknown fields {sorted(unknown)}"


# ---------------------------------------------------------------------------
# spec-hash invariances (representation must NOT flip the hash)
# ---------------------------------------------------------------------------
def test_hash_invariant_to_dict_ordering():
    echo = _BASE_SPEC.replace(run=RunConfig(protocol="softsync",
                                            n_softsync=2,
                                            n_learners=4)).echo()
    shuffled = {k: echo[k] for k in reversed(list(echo))}
    shuffled["run"] = {k: echo["run"][k] for k in reversed(list(echo["run"]))}
    assert spec_hash_from_echo(echo) == spec_hash_from_echo(shuffled)


def test_hash_invariant_to_json_roundtrip():
    spec = ExperimentSpec(
        run=RunConfig(protocol="softsync", n_softsync=4, n_learners=16,
                      serving=FleetConfig(replicas=3)),
        problem="mlp_teacher", epochs=2.0, eval_every=50, tag="rt")
    echo = json.loads(json.dumps(spec.echo(), default=float))
    assert spec_hash(spec) == spec_hash_from_echo(echo)


def test_hash_invariant_to_float_formatting():
    a = ExperimentSpec(run=RunConfig(), problem="mlp_teacher", epochs=6.0)
    echo = a.echo()
    echo["epochs"] = 6          # int vs 6.0: same epoch budget
    assert spec_hash(a) == spec_hash_from_echo(echo)
    echo["run"]["momentum"] = 0.9 + 0.0   # still the default -> pruned
    assert spec_hash(a) == spec_hash_from_echo(echo)


def test_hash_invariant_to_default_materialization():
    # a record written before a field existed (field absent) must hash like
    # one written after (field present at its default)
    spec = ExperimentSpec(run=RunConfig(n_learners=4), steps=50)
    echo = spec.echo()
    trimmed = copy.deepcopy(echo)
    del trimmed["run"]["ref_batch"]       # pretend ref_batch predates echo
    del trimmed["eval_every"]
    assert spec_hash_from_echo(echo) == spec_hash_from_echo(trimmed)


def test_retired_field_echo_keeps_its_hash():
    # records stored before RunConfig lost ring_impl echo it at its only
    # stored value; they must keep the hash they were filed under
    spec = ExperimentSpec(run=RunConfig(n_learners=4), steps=50)
    echo = spec.echo()
    old = copy.deepcopy(echo)
    old["run"]["ring_impl"] = "auto"
    assert spec_hash_from_echo(old) == spec_hash_from_echo(echo)
    old["run"]["ring_impl"] = "pallas"      # not the default: a new address
    assert spec_hash_from_echo(old) != spec_hash_from_echo(echo)


def test_default_serving_fleet_is_not_pruned_to_none():
    # serving=FleetConfig() is a different experiment than serving=None
    # even though every FleetConfig field is at its default
    plain = ExperimentSpec(run=RunConfig(), steps=50)
    served = ExperimentSpec(run=RunConfig(serving=FleetConfig()), steps=50)
    assert spec_hash(plain) != spec_hash(served)
    assert canonical_echo(served.echo())["run"]["serving"] == {}


def test_measure_mode_and_problem_versions_reach_hash():
    measured = ExperimentSpec(run=RunConfig(), steps=100)
    trained = ExperimentSpec(run=RunConfig(), problem="mlp_teacher",
                             steps=100)
    assert spec_hash(measured) != spec_hash(trained)
    assert content_hash({"a": 1}) != content_hash({"a": 2})


# ---------------------------------------------------------------------------
# registry / DAG
# ---------------------------------------------------------------------------
def test_registry_rejects_duplicate_name_and_result(monkeypatch):
    monkeypatch.setattr(registry, "_CELLS", dict(registry._CELLS))
    cell = registry.Cell(name="dup_test", result="dup_test_result",
                         compute=lambda: ([], {}))
    registry.register_cell(cell)
    with pytest.raises(ValueError, match="dup_test"):
        registry.register_cell(registry.Cell(
            name="dup_test", result="other", compute=lambda: ([], {})))
    with pytest.raises(ValueError, match="dup_test_result"):
        registry.register_cell(registry.Cell(
            name="dup_test2", result="dup_test_result",
            compute=lambda: ([], {})))


def test_resolve_order_is_topological_and_detects_cycles(monkeypatch):
    monkeypatch.setattr(registry, "_CELLS", dict(registry._CELLS))
    for name, deps in [("t_a", ()), ("t_b", ("t_a",)), ("t_c", ("t_b",))]:
        registry.register_cell(registry.Cell(
            name=name, result=f"{name}_res", deps=deps,
            compute=lambda: ([], {}), campaigns=("t_camp",)))
    order = registry.resolve_order(["t_c"])
    assert order == ["t_a", "t_b", "t_c"]

    registry._CELLS["t_a"] = dataclasses.replace(
        registry._CELLS["t_a"], deps=("t_c",))
    with pytest.raises(ValueError, match="[Cc]ycle"):
        registry.resolve_order(["t_c"])


def test_paper_campaign_topology():
    cells = registry.cells_in("paper")
    seen = set()
    for cell in cells:
        for dep in cell.deps:
            assert dep in seen, (f"{cell.name} scheduled before its "
                                 f"dependency {dep}")
        seen.add(cell.name)
    # the summary cell consumes four other cells' envelopes; it must close
    # the paper campaign's DAG
    assert cells[-1].name == "table3_4"


def test_cell_hash_changes_with_params_and_version():
    cell = registry.get_cell("fig4")
    assert registry.cell_hash(cell) != registry.cell_hash(
        cell, {"steps": 123})
    bumped = dataclasses.replace(cell, version=cell.version + 1)
    assert registry.cell_hash(cell) != registry.cell_hash(bumped)


# ---------------------------------------------------------------------------
# execute / cache / resume / force on the smoke campaign
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def smoke_run(tmp_path_factory):
    """One executed smoke campaign in a temp results dir (module-scoped:
    the execution itself is the expensive part)."""
    rd = str(tmp_path_factory.mktemp("smoke_results"))
    ledger = campaign.run_campaign("smoke", quick=True, results_dir=rd,
                                   out=open(os.devnull, "w"))
    return rd, ledger


def test_smoke_campaign_executes_and_claims_pass(smoke_run):
    rd, ledger = smoke_run
    assert ledger["executed"] == 3 and ledger["cached"] == 0
    assert ledger["failed_claims"] == 0
    for name in ("smoke_grid", "smoke_measure", "smoke_report"):
        assert os.path.exists(os.path.join(rd, f"{name}.json"))


def test_second_pass_is_all_cache_hits(smoke_run):
    rd, _ = smoke_run
    ledger = campaign.run_campaign("smoke", quick=True, results_dir=rd,
                                   out=open(os.devnull, "w"))
    assert ledger["executed"] == 0 and ledger["cached"] == 3
    # cache hits must not re-run anything: the whole pass is file reads
    assert ledger["total_seconds"] < 5.0


def test_force_reexecutes_current_cells(smoke_run):
    rd, _ = smoke_run
    ledger = campaign.run_campaign("smoke", only=("smoke_measure",),
                                   force=True, quick=True, results_dir=rd,
                                   out=open(os.devnull, "w"))
    assert ledger["cells"]["smoke_measure"]["action"] == "executed"


def test_partial_sweep_resumes_reusing_cached_records(smoke_run):
    rd, _ = smoke_run
    cell = registry.get_cell("smoke_grid")
    path = registry.results_path(cell, rd)
    with open(path) as f:
        full = json.load(f)
    assert len(full["records"]) == 4    # 2 LRs x 2 seeds

    # truncate to a strict subset -> PARTIAL -> resume completes the grid
    partial = copy.deepcopy(full)
    partial["records"] = partial["records"][:2]
    partial["campaign"]["partial"] = True
    with open(path, "w") as f:
        json.dump(partial, f, indent=1, default=float)
    status, _ = campaign.cell_status(cell, None, True, rd)
    assert status == "PARTIAL"

    campaign.execute_cell(cell, quick=True, results_dir=rd)
    with open(path) as f:
        resumed = json.load(f)
    assert [r["spec_hash"] for r in resumed["records"]] == \
        [r["spec_hash"] for r in full["records"]]
    # the two surviving records ride through verbatim, not re-executed
    assert resumed["records"][:2] == partial["records"][:2]
    status, _ = campaign.cell_status(cell, None, True, rd)
    assert status == "CURRENT"


def test_stale_on_foreign_records(smoke_run):
    rd, _ = smoke_run
    cell = registry.get_cell("smoke_grid")
    path = registry.results_path(cell, rd)
    with open(path) as f:
        data = json.load(f)
    broken = copy.deepcopy(data)
    broken["records"][0]["spec_hash"] = "0" * 16
    with open(path, "w") as f:
        json.dump(broken, f, indent=1, default=float)
    try:
        status, _ = campaign.cell_status(cell, None, True, rd)
        assert status == "STALE"
    finally:
        with open(path, "w") as f:
            json.dump(data, f, indent=1, default=float)


def test_run_cell_returns_derived(smoke_run):
    rd, _ = smoke_run
    derived = campaign.run_cell("smoke_grid", force=False, quick=True,
                                results_dir=rd)
    assert np.isfinite(derived["mean_test_error"])
    assert derived["claims"]["all_errors_finite"] is True


def test_cli_dry_run_and_status_json(smoke_run, tmp_path):
    rd, _ = smoke_run
    status_json = str(tmp_path / "status.json")
    rc = campaign.main(["smoke", "--dry-run", "--quick",
                        "--results-dir", rd, "--status-json", status_json])
    assert rc == 0
    with open(status_json) as f:
        ledger = json.load(f)
    assert ledger["cached"] == 3 and ledger["executed"] == 0


# ---------------------------------------------------------------------------
# migration pins: checked-in envelopes vs the registry
# ---------------------------------------------------------------------------
_SPEC_CELLS = ("fig4", "fig5", "fig6_7", "table2", "topology", "elastic",
               "serve")


@pytest.mark.parametrize("name", _SPEC_CELLS)
def test_checked_in_records_match_registered_specs(name):
    """The ported cell spec-graphs reproduce the legacy grids EXACTLY: the
    registry's spec hashes equal the migrated records' stamped hashes,
    which were computed from each record's own pre-campaign echo.  This is
    the byte-identity pin for the benchmark -> cell migration."""
    cell = registry.get_cell(name)
    with open(registry.results_path(cell, RESULTS_DIR)) as f:
        env = json.load(f)
    stamped = [r["spec_hash"] for r in env["records"]]
    assert stamped == registry.cell_spec_hashes(cell)
    for rec in env["records"]:
        assert spec_hash_from_echo(rec["spec"]) == rec["spec_hash"]


def test_all_paper_envelopes_current():
    for cell in registry.cells_in("paper"):
        status, detail = campaign.cell_status(cell,
                                              results_dir=RESULTS_DIR)
        assert status == "CURRENT", f"{cell.name}: {status} ({detail})"


def test_envelopes_carry_campaign_stamp():
    for cell in registry.cells_in("paper"):
        data = registry.load_envelope(cell, RESULTS_DIR)
        assert data["schema_version"] == SCHEMA_VERSION
        assert data["cell"] == cell.name
        assert data["campaign"]["cell_hash"] == registry.cell_hash(cell)


# ---------------------------------------------------------------------------
# validate: staleness + --migrate
# ---------------------------------------------------------------------------
def _copy_envelope(tmp_path, name="fig4"):
    cell = registry.get_cell(name)
    src = registry.results_path(cell, RESULTS_DIR)
    dst = os.path.join(str(tmp_path), os.path.basename(src))
    with open(src) as f:
        data = json.load(f)
    with open(dst, "w") as f:
        json.dump(data, f, indent=1, default=float)
    return dst, data


def test_validate_flags_legacy_envelope_and_migrates(tmp_path):
    dst, data = _copy_envelope(tmp_path)
    legacy = copy.deepcopy(data)
    legacy["schema_version"] = 1
    legacy.pop("cell", None)
    legacy.pop("campaign", None)
    for rec in legacy["records"]:
        rec.pop("spec_hash", None)
    with open(dst, "w") as f:
        json.dump(legacy, f, indent=1, default=float)

    rows = validate.staleness_report([str(tmp_path)])
    assert rows[0][1] == "STALE"
    assert validate.main([str(tmp_path), "--strict"]) == 1
    assert validate.main([str(tmp_path)]) == 0      # warn-only without strict

    assert validate.migrate_file(dst) == "migrated"
    with open(dst) as f:
        migrated = json.load(f)
    assert migrated == data                          # round-trips exactly
    assert validate.migrate_file(dst) == "current"   # idempotent
    assert validate.main([str(tmp_path), "--strict"]) == 0


def test_validate_flags_mismatched_record_hash(tmp_path):
    dst, data = _copy_envelope(tmp_path)
    data["records"][0]["spec_hash"] = "f" * 16
    with open(dst, "w") as f:
        json.dump(data, f, indent=1, default=float)
    rows = validate.staleness_report([str(tmp_path)])
    assert rows[0][1] == "STALE"


def test_validate_ignores_unregistered_files(tmp_path):
    with open(tmp_path / "adhoc.json", "w") as f:
        json.dump({"schema_version": SCHEMA_VERSION, "benchmark": "adhoc",
                   "records": [], "derived": {}, "cell": None,
                   "campaign": None}, f)
    rows = validate.staleness_report([str(tmp_path)])
    assert rows[0][1] == "UNREGISTERED"
    assert validate.main([str(tmp_path), "--strict"]) == 0
