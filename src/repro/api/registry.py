"""Name → implementation registries for protocols and serving policies.

Two pluggable surfaces share one mechanism:

* **Protocol strategies** package the four protocol-specific training
  ingredients — epoch planning, batch assembly, the step function, and the
  end-of-round aggregation hook — behind one interface, so every protocol
  (CL / SL / FL / SFL / PSL, and future variants like CycleSL or GAPSL) is
  driven by the same training loop in :mod:`repro.api.loop`.
* **Serving policies** are the server-side axes of the continuous-batching
  runtime (the CycleSL lesson: the server-side policy is the pluggable
  part): admission order (``@register_scheduler_policy``), the budget
  controller (``@register_admission_policy``), and the engine itself
  (``@register_engine`` — continuous slot-pool vs the static A/B baseline).

Adding a scenario costs one registry entry::

    @register_protocol("cyclesl")
    class CycleSLStrategy(ProtocolStrategy):
        ...

    @register_scheduler_policy("sjf")
    class ShortestJobFirst:
        def order(self, ready):
            ready.sort(key=lambda r: r.max_new_tokens)

and is immediately reachable from JSON specs (``protocol.name``,
``scheduler.policy``, ``engine.name``, …), the CLIs, and the benchmarks.
Built-ins register as an import side effect of their home module
(:mod:`repro.api.protocols`, :mod:`repro.runtime`), imported lazily on
first lookup to avoid registry ↔ implementation import cycles.
"""
from __future__ import annotations

import importlib
from typing import Any, Dict, Iterator, List, Optional, Tuple, Type


class UnknownProtocolError(KeyError):
    """Lookup of a protocol name that was never registered."""


class UnknownPolicyError(KeyError):
    """Lookup of a serving policy/engine name that was never registered."""


class _Registry:
    """One name → implementation table with lazy built-in loading."""

    def __init__(self, kind: str, builtins_module: str, error_cls):
        self.kind = kind
        self._builtins_module = builtins_module
        self._error_cls = error_cls
        self._loaded = False
        self._entries: Dict[str, Any] = {}

    def register(self, name: str, *, replace: bool = False):
        """Decorator: make a class reachable by ``name`` (sets ``cls.name``)."""
        def deco(obj):
            if name in self._entries and not replace:
                raise ValueError(
                    f"{self.kind} {name!r} already registered "
                    f"({self._entries[name].__name__}); pass replace=True "
                    f"to override")
            obj.name = name
            self._entries[name] = obj
            return obj
        return deco

    def get(self, name: str):
        self._ensure_builtins()
        try:
            return self._entries[name]
        except KeyError:
            raise self._error_cls(
                f"unknown {self.kind} {name!r}; registered: "
                f"{self.available()}") from None

    def available(self) -> List[str]:
        self._ensure_builtins()
        return sorted(self._entries)

    def pop(self, name: str, default=None):
        """Remove an entry (test cleanup for throwaway registrations)."""
        return self._entries.pop(name, default)

    def _ensure_builtins(self) -> None:
        # registering the built-ins is an import side effect of the home
        # module; import lazily so registry<->implementation cycles never
        # form at module load. A flag, not an emptiness check: a custom
        # entry registered before the first lookup must not shadow the
        # built-ins.
        if not self._loaded:
            self._loaded = True
            importlib.import_module(self._builtins_module)


_PROTOCOLS = _Registry("protocol", "repro.api.protocols",
                       UnknownProtocolError)
# importing the repro.runtime package pulls in queue/scheduler/engine/static,
# which registers every built-in serving policy and engine
_SCHEDULER_POLICIES = _Registry("scheduler policy", "repro.runtime",
                                UnknownPolicyError)
_ADMISSION_POLICIES = _Registry("admission policy", "repro.runtime",
                                UnknownPolicyError)
_ENGINES = _Registry("serve engine", "repro.runtime", UnknownPolicyError)


def register_protocol(name: str, *, replace: bool = False):
    """Class decorator: make a :class:`ProtocolStrategy` reachable by name."""
    return _PROTOCOLS.register(name, replace=replace)


def get_protocol(name: str) -> Type["ProtocolStrategy"]:
    return _PROTOCOLS.get(name)


def available_protocols() -> List[str]:
    return _PROTOCOLS.available()


def register_scheduler_policy(name: str, *, replace: bool = False):
    """Class decorator: an admission-order policy (``order(ready)``)."""
    return _SCHEDULER_POLICIES.register(name, replace=replace)


def get_scheduler_policy(name: str):
    return _SCHEDULER_POLICIES.get(name)


def available_scheduler_policies() -> List[str]:
    return _SCHEDULER_POLICIES.available()


def register_admission_policy(name: str, *, replace: bool = False):
    """Class decorator: a budget controller (``grants``/``note_step``)."""
    return _ADMISSION_POLICIES.register(name, replace=replace)


def get_admission_policy(name: str):
    return _ADMISSION_POLICIES.get(name)


def available_admission_policies() -> List[str]:
    return _ADMISSION_POLICIES.available()


def register_engine(name: str, *, replace: bool = False):
    """Class decorator: a serve engine (``from_spec``/``serve``)."""
    return _ENGINES.register(name, replace=replace)


def get_engine(name: str):
    return _ENGINES.get(name)


def available_engines() -> List[str]:
    return _ENGINES.available()


class StepItem:
    """One unit of work yielded by a strategy's batch assembly.

    ``batch`` is whatever the strategy's ``step`` consumes; ``scope`` tags
    the sub-context (e.g. the client id in SL/FL/SFL; None for global
    streams); ``info`` carries per-step diagnostics (e.g. straggler arrival
    timing) that the loop forwards to callbacks on the step event.
    """

    __slots__ = ("batch", "scope", "info")

    def __init__(self, batch: Any, scope: Any = None,
                 info: Optional[Dict[str, Any]] = None):
        self.batch = batch
        self.scope = scope
        self.info = info


class ProtocolStrategy:
    """Interface the shared loop (repro.api.loop.fit) drives.

    One instance serves one run; put per-run mutable state (RNGs, engines,
    jitted steps) in the *protocol state* returned by :meth:`setup` or on
    the instance. The loop calls, per epoch::

        plan  = strategy.plan_epoch(ctx, epoch)           # may be None
        for item in strategy.epoch_batches(ctx, pstate, plan, epoch):
            pstate, metrics = strategy.step(ctx, pstate, item)
        pstate = strategy.end_epoch(ctx, pstate, epoch)   # aggregation hook

    and evaluates ``strategy.eval_params(ctx, pstate)`` on the epoch-end
    event.

    A strategy may donate its state to its step (CL, SL and the fused
    PSL step do), which deletes the state's arrays. So the params handed
    to ``epoch_end`` and ``run_end`` callbacks are valid until the next
    step: a callback that keeps them for later copies them first.
    """

    name: str = "?"

    def setup(self, ctx) -> Any:
        raise NotImplementedError

    def plan_epoch(self, ctx, epoch: int):
        return None

    def epoch_batches(self, ctx, pstate, plan, epoch: int
                      ) -> Iterator[StepItem]:
        raise NotImplementedError

    def step(self, ctx, pstate, item: StepItem) -> Tuple[Any, Dict]:
        raise NotImplementedError

    def end_epoch(self, ctx, pstate, epoch: int) -> Any:
        return pstate

    def eval_params(self, ctx, pstate) -> Any:
        raise NotImplementedError

    def finalize(self, ctx, pstate, record) -> None:
        """Last hook before run_end; may write protocol extras."""
