"""The group programs name their phases on the device: each phase runs
under a ``jax.named_scope`` of its ``repro.obs.schema.PHASES`` name, which
shows in the compiled program's ``op_name`` metadata and nowhere else."""
import contextlib
import re

import jax
import pytest

from repro.fed.fleet.batched import (FleetConfig, FleetEngine,
                                     make_cohort_groups)
from repro.fed.fleet.workloads import client_num_samples, get_workload
from repro.obs.schema import PHASES

DEVICE_PHASES = ("grad_features", "selection", "local_sgd", "coreset_epochs")


@pytest.fixture(scope="module")
def fleet():
    # a char-LSTM: its feature pass is a scan of its own (an MLP's is a
    # few elementwise ops, which the compiler fuses into selection's)
    wl = get_workload("charlm")
    clients = wl.make_clients(n_clients=6, seed=0)
    params = wl.init(jax.random.PRNGKey(0))
    cids = list(range(len(clients)))
    budgets = {c: 9 if c % 2 else client_num_samples(clients[c])
               for c in cids}
    return wl, clients, params, cids, budgets


def _compiled(fleet, k_positive: bool, materialize_below: int = 256):
    wl, clients, params, cids, budgets = fleet
    cfg = FleetConfig(epochs=2, batch_size=8, seed=0,
                      materialize_below=materialize_below)
    engine = FleetEngine(wl, cfg)
    group = next(g for g in make_cohort_groups(clients, cids, budgets, cfg)
                 if (g.k > 0) == k_positive)
    program, args = engine.stage_group(params, group)
    return program.lower(*args).compile().as_text()


def _scopes(text: str) -> set:
    return {part for path in re.findall(r'op_name="([^"]*)"', text)
            for part in path.split("/")}


def _without_metadata(text: str) -> list:
    """The program's instructions with metadata, instruction numbering
    and the module's source-location tables left out."""
    out, tables = [], False
    for line in text.splitlines():
        if line.startswith("FileNames"):
            tables = True
        if tables and line.startswith(("%", "ENTRY")):
            tables = False
        if not tables:
            line = re.sub(r", metadata=\{[^{}]*\}", "", line)
            out.append(re.sub(r"\.\d+\b", "", line))
    return out


def test_device_phases_are_schema_phases():
    assert set(DEVICE_PHASES) <= set(PHASES)
    assert "stage" in PHASES


@pytest.mark.parametrize("materialize_below", [256, 0],
                         ids=["distances", "features"])
def test_coreset_group_program_names_every_phase(fleet, materialize_below):
    text = _compiled(fleet, True, materialize_below)
    assert set(DEVICE_PHASES) <= _scopes(text)
    # the solver's two stages, inside the selection scope
    for sub in ("build", "swap"):
        assert re.search(rf'op_name="[^"]*/selection/[^"]*/{sub}/', text)


def test_full_set_group_program_is_local_sgd(fleet):
    scopes = _scopes(_compiled(fleet, False))
    assert "local_sgd" in scopes
    assert not scopes & {"grad_features", "selection", "coreset_epochs"}


@pytest.mark.parametrize("k_positive", [False, True], ids=["k0", "k"])
def test_scopes_change_only_metadata(fleet, k_positive, monkeypatch):
    scoped = _compiled(fleet, k_positive)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _compiled(fleet, k_positive)
    assert "/local_sgd/" not in plain
    assert _without_metadata(scoped) == _without_metadata(plain)
