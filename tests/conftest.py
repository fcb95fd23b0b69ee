"""Test configuration.

Tests run on CPU with 8 virtual devices so multi-chip sharding (tp/dp/sp meshes)
is exercised without TPU hardware — mirrors the reference's tier-1 strategy of
pure-host unit tests (/root/reference: SURVEY.md section 4).

Env vars must be set before the first jax import; JAX_PLATFORMS=cpu in the
environment is all it takes to hold JAX to the CPU.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    import jax

    return jax.random.PRNGKey(0)


@pytest.fixture
def step_spans(monkeypatch):
    """The ids of every zero-length `bbtpu.step` span the executor stamps
    while the test runs (runtime/executor.py `_keep_arena`; `BBTPU_JITWATCH`
    on), in order."""
    from bloombee_tpu.utils import jitwatch

    monkeypatch.setenv("BBTPU_JITWATCH", "1")
    seen = []
    real = jitwatch.span

    def stamped(name, **ids):
        if name == "bbtpu.step":
            seen.append(ids)
        return real(name, **ids)

    monkeypatch.setattr(jitwatch, "span", stamped)
    return seen


def _is_cell_rehearsal(item) -> bool:
    return (item.path.name == "test_cell_rehearsal.py"
            and item.name.startswith("test_cell_rehearsal_"))


@pytest.hookimpl(trylast=True)
def pytest_collection_modifyitems(items):
    """Deal the cell rehearsals (tests/test_cell_rehearsal.py: 25 cases of
    about a minute, each a swarm of processes) through the first four fifths
    of the collection, each at a boundary between two modules.

    xdist's `load` hands a worker CONSECUTIVE tests, some fifty at a time at
    the start of a run of this size, so cases that stand in a row queue on
    one or two workers while the others run out of work; dealt out, two or
    three run at once and none starts in the run's last minutes. A module's
    own tests stay together (its module fixtures are built once). Every
    worker collects the same list, so every worker deals the same way.
    """
    heavy = [it for it in items if _is_cell_rehearsal(it)]
    rest = [it for it in items if not _is_cell_rehearsal(it)]
    bounds = [k for k, it in enumerate(rest)
              if k == 0 or it.path != rest[k - 1].path]
    if not heavy or len(bounds) < 2:
        return
    at: dict[int, list] = {}
    for j, it in enumerate(heavy):
        want = j * 0.8 * len(rest) / len(heavy)
        at.setdefault(min(bounds, key=lambda k: abs(k - want)), []).append(it)
    items[:] = [it for k, one in enumerate(rest) for it in (*at.get(k, ()), one)]
