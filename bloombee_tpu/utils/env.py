"""Structured env/flag layer.

The reference exposes ~60 ``BLOOMBEE_*`` switches through an ad-hoc
``os.environ`` scatter plus utils/debug_config.py:62-120 (group toggles and
named log channels). Here every switch is declared once in a registry with a
type, default, and help string, so ``describe()`` can print the authoritative
table (the role of the reference's README.environment-switches.md) and typos
in switch names are detectable instead of silently ignored.

Switches use the ``BBTPU_`` prefix. Reading is cheap (plain os.environ) and
uncached by default so tests can monkeypatch the environment.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class Flag:
    name: str  # full env var name, e.g. BBTPU_DEBUG
    kind: type  # bool | int | float | str
    default: object
    help: str


_REGISTRY: dict[str, Flag] = {}


def declare(name: str, kind: type, default, help_: str) -> Flag:
    """Register a switch. Called by the module that reads the switch, next to
    the code it controls, so the registry can never contain no-op entries."""
    flag = Flag(name, kind, default, help_)
    _REGISTRY[name] = flag
    return flag


def _parse(flag: Flag, raw: str):
    if flag.kind is bool:
        return raw.strip().lower() in ("1", "true", "yes", "on")
    try:
        return flag.kind(raw)
    except ValueError:
        logger.warning(
            "ignoring unparsable %s=%r (want %s)", flag.name, raw,
            flag.kind.__name__,
        )
        return flag.default


# One thread at a time pulls in the declaring modules: a client process that
# opens its first sessions from several threads at once (one thread a session
# in cellbench/loadgen.py) otherwise imports one package from two threads,
# and the loser sees it half-initialised ("cannot import name ... from
# partially initialized module"). Reentrant: a module imported here may read
# a switch while it loads.
_IMPORTING = threading.RLock()


def get(name: str):
    """Read a declared flag from the environment (or its default)."""
    flag = _REGISTRY.get(name)
    if flag is None:
        # Some switches are read in more modules than the one declaring
        # them (e.g. a pure-client process reading BBTPU_PREFIX_CACHE,
        # declared next to the server-side pool it also controls). Pull
        # in the declaring modules once; only a genuinely unknown name —
        # a typo — still fails loudly after that.
        with _IMPORTING:
            import_declaring_modules()
        flag = _REGISTRY[name]
    raw = os.environ.get(flag.name)
    if raw is None:
        return flag.default
    return _parse(flag, raw)


def import_declaring_modules() -> None:
    """Import every module that declares switches so describe() is complete
    (kept here, next to the registry, so new declare() sites only need to
    be added in one place)."""
    import bloombee_tpu.client.session  # noqa: F401
    import bloombee_tpu.kv.cache_manager  # noqa: F401
    import bloombee_tpu.models.hub  # noqa: F401
    import bloombee_tpu.runtime.executor  # noqa: F401
    import bloombee_tpu.server.admission  # noqa: F401
    import bloombee_tpu.server.artifacts  # noqa: F401
    import bloombee_tpu.server.block_selection  # noqa: F401
    import bloombee_tpu.server.block_server  # noqa: F401
    import bloombee_tpu.sim.metrics  # noqa: F401
    import bloombee_tpu.sim.scenarios  # noqa: F401
    import bloombee_tpu.utils.clock  # noqa: F401
    import bloombee_tpu.utils.jitwatch  # noqa: F401
    import bloombee_tpu.utils.ledger  # noqa: F401
    import bloombee_tpu.utils.lockwatch  # noqa: F401
    import bloombee_tpu.wire.faults  # noqa: F401
    import bloombee_tpu.wire.pipeline  # noqa: F401
    import bloombee_tpu.wire.tensor_codec  # noqa: F401


def describe() -> str:
    """Authoritative flag table (reference README.environment-switches.md)."""
    lines = ["| switch | type | default | description |", "|---|---|---|---|"]
    for flag in sorted(_REGISTRY.values(), key=lambda f: f.name):
        lines.append(
            f"| {flag.name} | {flag.kind.__name__} | {flag.default!r} "
            f"| {flag.help} |"
        )
    return "\n".join(lines)


# Flags read by this module itself; feature modules declare their own
# switches next to the code that reads them.
declare("BBTPU_DEBUG", bool, False, "enable all debug log channels")
declare(
    "BBTPU_LOG_CHANNELS", str, "",
    "comma-separated debug channels (wire, kv, microbatch, spec, timing)",
)


def log_channel_enabled(channel: str) -> bool:
    """Named debug channels (reference debug_config named log channels)."""
    if get("BBTPU_DEBUG"):
        return True
    raw = get("BBTPU_LOG_CHANNELS")
    return channel in tuple(c.strip() for c in raw.split(",") if c.strip())
