"""Runtime sanitizers: composable context managers that turn the
repo's device-residency and compile-set claims into hard failures.

Three guards, one per correctness surface:

- ``compile_budget(n)`` — counts XLA backend compiles inside the block
  (via ``jax.monitoring``'s ``backend_compile_duration`` event, which
  fires exactly once per XLA compilation, cache hits excluded) and
  raises ``CompileBudgetExceeded`` on overrun. With ``log_names=True``
  it additionally flips ``jax_log_compiles`` and captures the
  ``jit(<name>)`` labels from the dispatch log so an overrun names the
  offending programs. This is what pins the ROADMAP compile-tax item:
  under pow2 shape quantization a churn timeline must stay within
  O(log population) distinct programs, not O(rounds).
- ``no_transfer()`` — zero implicit host↔device transfers inside the
  block (``jax.transfer_guard("disallow")``), generalizing the one-off
  proof in ``tests/test_device_clustering.py`` to any code region.
  Explicit escapes (``jax.device_put``, ``np.asarray(arr)`` on a
  committed array) still fail — that is the point.
- ``nan_guard()`` — flips ``jax_debug_nans`` for the block, so any
  NaN/Inf produced inside a jitted computation re-runs op-by-op and
  raises at the producing primitive instead of poisoning the round
  loop silently.

All three restore prior global state on exit and nest/compose freely::

    with sanitize.no_transfer(), sanitize.compile_budget(4) as log:
        state = engine.run_rounds(...)
    assert log.count <= 4
"""
from __future__ import annotations

import contextlib
import dataclasses
import logging
import re
from typing import Iterator, List, Optional

import jax

__all__ = ["CompileLog", "CompileBudgetExceeded", "compile_budget",
           "no_transfer", "nan_guard"]

# fires once per XLA backend compilation (jax._src.dispatch wraps every
# backend.compile in record_event_duration_secs with this key)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# fires once per persistent-compilation-cache hit. NOTE the compile
# event above wraps compile_or_get_cached, so it fires for EVERY
# compile request, served-from-cache or not — ``count`` is "programs
# requested", and ``cache_hits`` says how many of those skipped the
# actual XLA compile (warm process: cache_hits == count)
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_LOG_NAME_RE = re.compile(
    r"Finished XLA compilation of (\S+) in [\d.e+-]+ sec")


class CompileBudgetExceeded(AssertionError):
    """Raised when a ``compile_budget(n)`` block triggers more than
    ``n`` XLA compilations."""


@dataclasses.dataclass
class CompileLog:
    """Live compile tally for a ``compile_budget`` block: ``count`` is
    authoritative (monitoring event, one per XLA compile); ``names``
    lists ``jit(<label>)`` strings when ``log_names=True`` captured
    them (diagnostic only — the log line and the event are emitted by
    different layers)."""
    budget: Optional[int] = None
    count: int = 0
    cache_hits: int = 0     # persistent-compilation-cache serves (no XLA run)
    seconds: float = 0.0    # summed backend-compile durations
    names: List[str] = dataclasses.field(default_factory=list)

    def describe(self) -> str:
        """Human-readable tally, naming compiled programs when
        known."""
        head = f"{self.count} XLA compile(s)"
        if self.budget is not None:
            head += f" (budget {self.budget})"
        if self.names:
            head += ": " + ", ".join(self.names)
        return head


class _LogHandler(logging.Handler):
    def __init__(self, log: CompileLog):
        super().__init__(level=logging.DEBUG)
        self._log = log

    def emit(self, record):
        m = _LOG_NAME_RE.search(record.getMessage())
        if m:
            self._log.names.append(m.group(1))


@contextlib.contextmanager
def compile_budget(budget: Optional[int] = None, *,
                   log_names: bool = False) -> Iterator[CompileLog]:
    """Count XLA compiles in the block; raise ``CompileBudgetExceeded``
    if they exceed ``budget`` (``None`` = just count). The yielded
    ``CompileLog`` updates live, so callers can also assert mid-block
    or record counts into benchmarks. ``log.cache_hits`` separately
    tallies persistent-compilation-cache serves; a served request STILL
    fires the compile event (the event wraps compile_or_get_cached), so
    the warm-start assertion is ``cache_hits == count`` — every program
    requested, none actually compiled."""
    log = CompileLog(budget=budget)

    def _on_event(event: str, duration: float, **kw) -> None:
        if event == _COMPILE_EVENT:
            log.count += 1
            log.seconds += duration

    def _on_hit(event: str, **kw) -> None:
        if event == _CACHE_HIT_EVENT:
            log.cache_hits += 1

    jax.monitoring.register_event_duration_secs_listener(_on_event)
    jax.monitoring.register_event_listener(_on_hit)
    handler = None
    prev_log_compiles = None
    logger = logging.getLogger("jax._src.dispatch")
    if log_names:
        prev_log_compiles = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        handler = _LogHandler(log)
        logger.addHandler(handler)
    try:
        yield log
    finally:
        jax.monitoring.unregister_event_duration_listener(_on_event)
        jax.monitoring.unregister_event_listener(_on_hit)
        if handler is not None:
            logger.removeHandler(handler)
            jax.config.update("jax_log_compiles", prev_log_compiles)
    if budget is not None and log.count > budget:
        raise CompileBudgetExceeded(
            f"compile budget exceeded: {log.describe()}")


@contextlib.contextmanager
def no_transfer() -> Iterator[None]:
    """Disallow implicit host↔device transfers inside the block.

    Any device→host sync (``float(arr)``, ``np.asarray(arr)``,
    ``.item()``) or implicit host→device upload raises — the runtime
    twin of the linter's R2 rule, and the guard the per-strategy
    zero-transfer battery runs the scanned round step under."""
    with jax.transfer_guard("disallow"):
        yield


@contextlib.contextmanager
def nan_guard() -> Iterator[None]:
    """Fail loudly on NaN/Inf from any jitted computation inside the
    block (``jax_debug_nans``); prior flag state is restored on
    exit."""
    prev = jax.config.jax_debug_nans
    jax.config.update("jax_debug_nans", True)
    try:
        yield
    finally:
        jax.config.update("jax_debug_nans", prev)
