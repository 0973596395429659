"""Chaos engine — deterministic, seeded fault injection (ISSUE 3 tentpole).

The reference's failure semantics (RedisExecutor's retry state machine,
typed exceptions — PAPER.md §5 failure row) are only a contract if the
failure paths can be *driven*.  This package threads named fault points
through every device boundary and lets a reproducible schedule raise a
chosen exception, inject latency, or corrupt-and-detect at each one.

Fault-point catalog (see docs/robustness.md):

======================  ====================================================
point                   where it fires
======================  ====================================================
``dispatch.<method>``   inside the executor dispatch wrapper (``_locked``
                        in tpu_executor.py — shared with the sharded
                        executor), per method:
                        ``dispatch.bloom_mixed_keys``,
                        ``dispatch.read_row``, ...  A rule installed under
                        the bare prefix ``dispatch`` matches every method.
``fetch``               completion / D2H result fetch (LazyResult.result)
``h2d.staging``         pinned-staging H2D ship (``_put_staged``)
``h2d.scatter``         sharded scatter staging (``_scatter_put``,
                        the Bloom block ``_bloom_put``)
``prewarm``             AOT bucket pre-warm worker, before each warm call
``snapshot.save``       snapshot()/dump() I/O
``snapshot.load``       restore_snapshot()/restore() I/O
``snapshot.rename``     between the snapshot tmp-file fsync and its rename
                        (the crash window the ISSUE 10 satellite closes)
``journal.write``       op-journal writer, before each group-commit batch
                        write (durability/journal.py)
``journal.fsync``       before each journal fsync (latency rules here
                        inflate the admission lag estimate under
                        appendfsync=always)
``journal.torn_tail``   per journal frame: when it fires, HALF the frame
                        reaches the file and the journal breaks — the
                        crash-mid-write simulation recovery must truncate
``repl.stream``         primary side, per RTPU.REPLFETCH batch
                        (serve/resp.py): ``error`` drops the batch (an
                        empty reply — the replica retries), ``corrupt``
                        flips a payload byte so the replica's CRC check
                        rejects the batch, ``latency`` delays the reply
``repl.ack``            primary side, per REPLCONF ACK: ``error``/
                        ``corrupt`` drop the ack (the WAIT fence and
                        INFO lag stay stale until the next one lands)
======================  ====================================================

Zero-overhead-when-disabled contract: every call site is guarded by the
module-level ``if chaos.ENABLED:`` check — ONE module-attribute read and a
branch, nothing else (verified by tests/test_chaos.py's disabled-overhead
guard).  ``fire()`` is only ever entered while a schedule is installed.

Determinism: each rule owns a ``random.Random`` seeded from
``(schedule seed, point)`` and a call counter, both advanced under the
rule's lock — the fire/skip sequence per point is a pure function of
(seed, rate, per-point call index), independent of cross-point thread
interleaving.
"""

from __future__ import annotations

import threading
import zlib
from typing import Optional

from redisson_tpu.chaos.schedule import ChaosSchedule, FaultRule

# Module-level no-op guard: hot paths check this BEFORE calling fire().
ENABLED = False

_lock = threading.Lock()
_rules: dict[str, FaultRule] = {}
_counts: dict[tuple, int] = {}  # (point, kind) -> faults actually injected
_observer = None  # callable(point, kind) — obs counter wiring (engine sets)

KINDS = ("error", "latency", "corrupt", "pressure")


class FaultInjected(RuntimeError):
    """A chaos rule fired with kind='error' — a deliberate, retryable
    dispatch-surface failure (the generic transient-device stand-in)."""

    def __init__(self, point: str):
        super().__init__(f"chaos: injected fault at {point!r}")
        self.point = point


class CorruptionDetected(RuntimeError):
    """A chaos rule fired with kind='corrupt': the engine flipped a bit in
    a shadow copy of the payload, verified the checksum catches it, and
    surfaces the detection — the torn-transfer / bad-DMA stand-in."""

    def __init__(self, point: str):
        super().__init__(f"chaos: corruption detected at {point!r}")
        self.point = point


# -- schedule management -----------------------------------------------------


def install(schedule: ChaosSchedule) -> None:
    """Replace the active rule set with ``schedule`` and arm the guard."""
    global ENABLED
    with _lock:
        _rules.clear()
        for rule in schedule.rules():
            _rules[rule.point] = rule
        ENABLED = bool(_rules)


def inject(point: str, kind: str = "error", rate: float = 1.0,
           seed: int = 0, latency_s: float = 0.001) -> None:
    """Add/replace ONE rule (the DEBUG INJECT surface).  ``point`` may be
    a catalog name, a ``dispatch.<method>`` refinement, or ``*``."""
    global ENABLED
    if kind not in KINDS:
        raise ValueError(f"unknown fault kind {kind!r} (want one of {KINDS})")
    with _lock:
        _rules[point] = FaultRule(
            point, kind=kind, rate=float(rate), seed=int(seed),
            latency_s=float(latency_s),
        )
        ENABLED = True


def clear(point: Optional[str] = None) -> None:
    """Remove one rule, or every rule (DEBUG INJECT OFF).  Disarms the
    module guard when nothing remains — disabled means ZERO work at every
    fault point beyond the guard branch itself."""
    global ENABLED
    with _lock:
        if point is None:
            _rules.clear()
        else:
            _rules.pop(point, None)
        ENABLED = bool(_rules)


def active() -> dict:
    """{point: (kind, rate, seed)} snapshot of the installed rules."""
    with _lock:
        return {
            p: (r.kind, r.rate, r.seed) for p, r in _rules.items()
        }


def counts() -> dict:
    """{(point, kind): injected} — faults that actually fired."""
    with _lock:
        return dict(_counts)


def reset_counts() -> None:
    with _lock:
        _counts.clear()


def set_observer(fn) -> None:
    """Wire an obs counter: ``fn(point, kind)`` runs per injected fault
    (the engine points this at ``rtpu_faults_injected``)."""
    global _observer
    _observer = fn


def unset_observer(fn) -> None:
    """Unhook ``fn`` if it is still the active observer (engine
    shutdown: a later engine's observer must not be clobbered, and a
    dangling one must not pin a dead engine in this module global)."""
    global _observer
    if _observer is fn:
        _observer = None


# -- the fault point ---------------------------------------------------------


def _match(point: str) -> Optional[FaultRule]:
    rule = _rules.get(point)
    if rule is None and "." in point:
        rule = _rules.get(point.split(".", 1)[0])
    if rule is None:
        rule = _rules.get("*")
    return rule


def _roll_and_record(point: str) -> Optional[FaultRule]:
    """Shared match/roll/count/observer bookkeeping for fire() and
    bias(): the matched rule when its deterministic roll says inject
    (already counted and reported to the observer), else None."""
    rule = _match(point)
    if rule is None or not rule.roll():
        return None
    key = (point, rule.kind)
    with _lock:
        _counts[key] = _counts.get(key, 0) + 1
    obs = _observer
    if obs is not None:
        try:
            obs(point, rule.kind)
        except Exception:
            pass
    return rule


def fire(point: str, data=None) -> None:
    """Evaluate the schedule at a named fault point.  Only reachable when
    ``ENABLED`` is True (call sites guard); no-op when no rule matches or
    the rule's deterministic roll says pass."""
    rule = _roll_and_record(point)
    if rule is None:
        return
    if rule.kind == "pressure":
        # Pressure rules only act through bias() (wait-estimate
        # inflation); at an ordinary fault point they are inert — the
        # roll above still advanced, keeping the sequence deterministic.
        return
    if rule.kind == "latency":
        import time

        time.sleep(rule.latency_s)
        return
    if rule.kind == "corrupt":
        # Corrupt-AND-detect: flip one bit in a shadow copy of the payload
        # and prove the checksum catches it — the surfaced failure models
        # a transfer whose integrity check fired.  The live payload is
        # never touched (a detected corruption is discarded, not applied).
        if data is not None:
            try:
                import numpy as np

                buf = np.asarray(data).tobytes()
                if buf:
                    shadow = bytearray(buf)
                    shadow[rule.calls % len(shadow)] ^= 0x40
                    assert zlib.crc32(bytes(shadow)) != zlib.crc32(buf)
            except AssertionError:  # pragma: no cover — crc collision
                pass
            except Exception:  # pragma: no cover — non-buffer payload
                pass
        raise CorruptionDetected(point)
    raise FaultInjected(point)


def bias(point: str) -> float:
    """Deterministic estimate inflation for the overload control plane
    (ISSUE 7): evaluate the schedule at ``point`` and return the rule's
    ``latency_s`` as extra SECONDS to add to a wait estimate — no sleep,
    no exception, so the injection perturbs only the admission decision,
    never the op itself.  0.0 when disabled, unmatched, or the roll says
    pass.  Conventionally installed at ``overload.pressure`` with
    kind='pressure' (any kind works: only the magnitude is read)."""
    if not ENABLED:
        return 0.0
    rule = _roll_and_record(point)
    return rule.latency_s if rule is not None else 0.0


__all__ = [
    "ChaosSchedule",
    "CorruptionDetected",
    "ENABLED",
    "FaultInjected",
    "FaultRule",
    "KINDS",
    "active",
    "bias",
    "clear",
    "counts",
    "fire",
    "inject",
    "install",
    "reset_counts",
    "set_observer",
]
