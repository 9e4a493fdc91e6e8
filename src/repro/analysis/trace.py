"""Trace-time auditors (`repro.analysis.trace`, DESIGN.md §12).

Three runtime complements to the AST lint, each checking a property the lint
can only approximate syntactically:

  * `assert_traces(n, *targets)` — the reusable retrace counter. PR 5 proved
    the guided_fused step traces `forward_train` exactly once with a bespoke
    monkeypatch; this generalizes that machinery: a target is either a
    jit-wrapped function (counted via its compilation-cache growth — one new
    cache entry per trace) or a `(holder, "attr")` pair whose function is
    temporarily wrapped to count executions (a traced function's Python body
    runs once per trace). The block must produce exactly `n` traces in total.

  * `audit_dtypes(fn, *args)` — walks the jaxpr of `fn` (recursing into
    scan/cond/pjit/custom-call sub-jaxprs) and reports every equation where a
    float64 input meets a narrower float output. This is the machine check
    for the DESIGN.md §11 class of bug: an f32-casting fold silently
    truncating the f64 parity trajectory.

  * `audit_donation(args, donate_argnums)` — reports the non-donated
    arguments of a dispatch that are large enough to matter. The chunked
    trainloop donates its (params, gstate) carry end-to-end; this auditor is
    how a test proves that, and how a future loop's forgotten
    `donate_argnums` shows up as named buffers with byte sizes instead of a
    silent 2x memory footprint.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, List, Sequence, Tuple


class TraceCountError(AssertionError):
    """Raised by assert_traces when the observed trace count differs."""


class _Tracker:
    """Live trace-count across the targets of one assert_traces block."""

    def __init__(self):
        self._jitted: List[Tuple[Any, int]] = []   # (fn, cache size at enter)
        self._wrapped: List[List[int]] = []        # mutable call counters
        self.labels: List[str] = []

    @property
    def count(self) -> int:
        total = sum(fn._cache_size() - start for fn, start in self._jitted)
        total += sum(c[0] for c in self._wrapped)
        return total

    def breakdown(self) -> str:
        parts = []
        for (fn, start), label in zip(self._jitted,
                                      self.labels[: len(self._jitted)]):
            parts.append(f"{label}: {fn._cache_size() - start} new cache entries")
        for c, label in zip(self._wrapped, self.labels[len(self._jitted):]):
            parts.append(f"{label}: {c[0]} trace-time calls")
        return "; ".join(parts) or "no targets"


@contextlib.contextmanager
def assert_traces(n: int, *targets):
    """Assert exactly `n` traces happen across `targets` inside the block.

    Targets:
      * a jit-wrapped function (``jax.jit`` result): counted by compilation-
        cache growth — cache hits are free, every new (shape, dtype) trace
        adds one;
      * ``(holder, "attr")``: ``holder.attr`` is wrapped for the duration of
        the block and each execution counts — the PR 5 idiom for proving a
        model function is traced once inside a step, now reusable.

    Yields the tracker (``tracker.count`` is live) and raises
    `TraceCountError` with a per-target breakdown on mismatch.
    """
    if not targets:
        raise ValueError("assert_traces needs at least one target "
                         "(a jitted fn or a (holder, 'attr') pair)")
    tracker = _Tracker()
    jit_targets, wrap_targets = [], []
    for t in targets:
        if isinstance(t, tuple) and len(t) == 2 and isinstance(t[1], str):
            wrap_targets.append(t)
        elif hasattr(t, "_cache_size"):
            jit_targets.append(t)
        else:
            raise TypeError(
                f"assert_traces target {t!r} is neither a jit-wrapped "
                f"function (no _cache_size) nor a (holder, 'attr') pair")
    for fn in jit_targets:
        tracker._jitted.append((fn, fn._cache_size()))
        tracker.labels.append(getattr(fn, "__name__", repr(fn)))
    patched = []
    try:
        for holder, attr in wrap_targets:
            original = getattr(holder, attr)
            counter = [0]

            def wrapper(*a, __original=original, __counter=counter, **kw):
                __counter[0] += 1
                return __original(*a, **kw)

            setattr(holder, attr, wrapper)
            patched.append((holder, attr, original))
            tracker._wrapped.append(counter)
            tracker.labels.append(f"{getattr(holder, '__name__', holder)}.{attr}")
        yield tracker
        got = tracker.count
        if got != n:
            raise TraceCountError(
                f"expected exactly {n} trace(s), observed {got} "
                f"({tracker.breakdown()})")
    finally:
        for holder, attr, original in patched:
            setattr(holder, attr, original)


# ------------------------------------------------------------- dtype audit


@dataclasses.dataclass(frozen=True)
class DtypeViolation:
    """One jaxpr equation where float64 meets a narrower float output."""

    primitive: str
    path: str            # nesting chain, e.g. "pjit/scan"
    in_dtypes: Tuple[str, ...]
    out_dtypes: Tuple[str, ...]

    def format(self) -> str:
        return (f"{self.path or '<top>'}: {self.primitive} demotes "
                f"{'/'.join(self.in_dtypes)} -> {'/'.join(self.out_dtypes)}")


_NARROW = ("float32", "bfloat16", "float16")


def _subjaxprs(value):
    from jax.extend.core import ClosedJaxpr, Jaxpr

    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _subjaxprs(v)


def _walk_jaxpr(jaxpr, path: str, out: List[DtypeViolation]):
    for eqn in jaxpr.eqns:
        ins = [str(v.aval.dtype) for v in eqn.invars
               if hasattr(getattr(v, "aval", None), "dtype")]
        outs = [str(v.aval.dtype) for v in eqn.outvars
                if hasattr(getattr(v, "aval", None), "dtype")]
        if any(d == "float64" for d in ins) and any(d in _NARROW for d in outs):
            out.append(DtypeViolation(
                primitive=eqn.primitive.name, path=path,
                in_dtypes=tuple(ins), out_dtypes=tuple(outs)))
        sub_path = f"{path}/{eqn.primitive.name}" if path else eqn.primitive.name
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                _walk_jaxpr(sub, sub_path, out)


def audit_dtypes(fn, *args, **kwargs) -> List[DtypeViolation]:
    """Trace `fn(*args, **kwargs)` and report every equation (at any nesting
    depth — scan bodies, cond branches, inner pjits) where a float64 input
    produces a float32/bf16/f16 output. Empty list == the f64 trajectory
    survives end to end."""
    import jax

    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    out: List[DtypeViolation] = []
    _walk_jaxpr(closed.jaxpr, "", out)
    return out


def assert_no_demotion(fn, *args, **kwargs):
    """`audit_dtypes` that raises, listing each offending equation."""
    violations = audit_dtypes(fn, *args, **kwargs)
    if violations:
        raise AssertionError(
            "float64 reaches narrower float ops:\n  "
            + "\n  ".join(v.format() for v in violations))


# ---------------------------------------------------------- donation audit


@dataclasses.dataclass(frozen=True)
class DonationReport:
    """One non-donated dispatch argument above the size threshold."""

    argnum: int
    name: str
    nbytes: int

    def format(self) -> str:
        return (f"arg {self.argnum} ({self.name}): {self.nbytes} bytes "
                f"not donated")


def _tree_nbytes(tree) -> int:
    import jax

    total = 0
    for leaf in jax.tree.leaves(tree):
        dtype = getattr(leaf, "dtype", None)
        if dtype is not None and hasattr(leaf, "size"):
            total += int(leaf.size) * dtype.itemsize
    return total


def audit_donation(args: Sequence, donate_argnums: Sequence[int] = (),
                   min_bytes: int = 1 << 16,
                   names: Sequence[str] = None) -> List[DonationReport]:
    """Report the arguments of a dispatch that are NOT donated yet carry at
    least `min_bytes` of array data. `donate_argnums` mirrors the jax.jit
    argument; `names` (optional, parallel to `args`) labels the report.
    Data batches legitimately show up here (they are consumed, not carried);
    a params/opt-state carry showing up means the loop holds two copies of
    the train state."""
    donated = set(donate_argnums)
    reports = []
    for i, a in enumerate(args):
        if i in donated:
            continue
        nbytes = _tree_nbytes(a)
        if nbytes >= min_bytes:
            name = names[i] if names and i < len(names) else f"arg{i}"
            reports.append(DonationReport(argnum=i, name=name, nbytes=nbytes))
    return reports
