"""Reducer accumulators for incremental group-by.

Engine counterpart of the reference's ``src/engine/reduce.rs:22-38`` reducer set
(Count, IntSum/FloatSum/ArraySum, Unique, Min/ArgMin, Max/ArgMax, SortedTuple, Tuple,
Any, Stateful, Earliest, Latest), keeping its two styles: **semigroup** reducers
(commutative, retraction = subtraction — ``reduce.rs:40``) update from vectorized
per-batch partial aggregates; **multiset** reducers (``reduce.rs:50``) maintain a
value multiset and re-extract on change.

The **ordered** multiset reducers (``tuple``, ``ndarray``) keep one thing between
extracts besides the multiset: the group's entries in extract order (sort key,
then arrival). A group's first extract sorts its entries, as every extract once
did; a later one sorts only the entries created since and merges that run into
the kept order. While every entry of the group counts once, the values are read
off the order's own tuples and no entry is visited. The order is derived state:
it is not pickled, and an extract that finds none, or finds that the fold before
it did not track what it created (``_MultisetState.fresh``), rebuilds it with the
full sort.
"""

from __future__ import annotations

from typing import Any, Callable

import numpy as np

from pathway_tpu import observability as _obs
from pathway_tpu.internals.errors import ERROR
from pathway_tpu.internals.keys import _canonical_bytes


class ReducerImpl:
    """Per-group accumulator protocol."""

    #: semigroup reducers support vectorized batch partials
    semigroup = False

    def make(self) -> Any:
        raise NotImplementedError

    def update(self, state: Any, values: tuple, diff: int, time: int, seq: int) -> None:
        raise NotImplementedError

    def extract(self, state: Any) -> Any:
        raise NotImplementedError

    # non-semigroup: a tick's rows are prepared once (``block_rows``), then each
    # group folds its own rows of that block (``fold_rows``)
    def block_rows(self, arrays: list[np.ndarray], diffs: np.ndarray) -> Any:
        """What ``fold_rows`` needs of one tick's batch, built once for all its
        groups."""
        return arrays, diffs

    def fold_rows(self, state: Any, block: Any, rows: list[int], time: int, seq: int) -> Any:
        """Fold one group's ``rows`` (indices into the block, arrival order)
        into ``state``; row k carries sequence number ``seq + k``. The default is
        ``update`` row by row."""
        arrays, diffs = block
        for k, i in enumerate(rows):
            state = (
                self.update(
                    state, tuple(arr[i] for arr in arrays), int(diffs[i]), time, seq + k
                )
                or state
            )
        return state

    # semigroup only: partial over a slice of column arrays, then merge
    def batch_partial(self, cols: list[np.ndarray], diffs: np.ndarray, sl: slice) -> Any:
        raise NotImplementedError

    def merge_partial(self, state: Any, partial: Any) -> Any:
        raise NotImplementedError

    def grouped_partials(
        self,
        cols: list[np.ndarray],
        diffs: np.ndarray,
        order: np.ndarray,
        starts: np.ndarray,
    ) -> Any | None:
        """All-groups partials in one vectorized pass (``order`` sorts rows by
        group, ``starts`` marks group boundaries). Returns an indexable of one
        partial per group, or None to fall back to per-group ``batch_partial``."""
        return None

    #: accumulator representable as a flat numeric array, merged by addition —
    #: lets GroupByNode keep its whole state columnar (no per-group Python)
    columnar = False

    def grouped_partials_np(
        self,
        cols: list[np.ndarray],
        diffs: np.ndarray,
        order: np.ndarray,
        starts: np.ndarray,
    ) -> np.ndarray | None:
        """Columnar variant of ``grouped_partials``: one numeric array with a
        partial per group, or None when this batch's columns can't vectorize
        (object dtype)."""
        return None


class CountReducer(ReducerImpl):
    semigroup = True
    columnar = True

    def make(self):
        return 0

    def update(self, state, values, diff, time, seq):
        return state + diff

    def extract(self, state):
        return state

    def batch_partial(self, cols, diffs, sl):
        return int(diffs[sl].sum())

    def merge_partial(self, state, partial):
        return state + partial

    def grouped_partials(self, cols, diffs, order, starts):
        return np.add.reduceat(diffs[order], starts).tolist()

    def grouped_partials_np(self, cols, diffs, order, starts):
        return np.add.reduceat(diffs[order], starts)


class SumReducer(ReducerImpl):
    semigroup = True
    columnar = True

    def __init__(self, kind: str = "int"):
        self.kind = kind

    def make(self):
        return 0 if self.kind == "int" else 0.0

    def update(self, state, values, diff, time, seq):
        v = values[0]
        if v is ERROR or v is None:
            return state
        return state + diff * v

    def extract(self, state):
        return state

    def batch_partial(self, cols, diffs, sl):
        col = cols[0][sl]
        d = diffs[sl]
        if col.dtype == object:
            total = 0
            for v, dd in zip(col, d):
                if v is not ERROR and v is not None:
                    total += dd * v
            return total
        return (col * d).sum()

    def merge_partial(self, state, partial):
        return state + partial

    def grouped_partials(self, cols, diffs, order, starts):
        col = cols[0]
        if col.dtype == object:
            return None
        weighted = col[order] * diffs[order]
        return np.add.reduceat(weighted, starts).tolist()

    def grouped_partials_np(self, cols, diffs, order, starts):
        col = cols[0]
        if col.dtype == object or col.dtype.kind not in "iufb":
            return None
        weighted = col[order] * diffs[order]
        out = np.add.reduceat(weighted, starts)
        if self.kind == "float" and out.dtype.kind != "f":
            out = out.astype(np.float64)
        return out


class ArraySumReducer(ReducerImpl):
    def make(self):
        return None

    def update(self, state, values, diff, time, seq):
        v = values[0]
        contrib = np.asarray(v) * diff
        return contrib if state is None else state + contrib

    def extract(self, state):
        return state


class _MultisetState:
    __slots__ = ("items", "total", "order", "fresh", "plain")

    def __init__(self):
        # canonical-bytes -> [value, count, (time, seq) of the creating row]
        self.items: dict[bytes, list] = {}
        self.total = 0
        # ordered reducers only, derived from ``items`` and never pickled:
        # ``order`` holds a sort tuple ``(sort_key, (time, seq), value,
        # entry)`` per entry, sorted, as of the last extract (None: never
        # extracted here); ``fresh`` the entries created since, in arrival
        # order (None: no tracking fold has run since that extract, so
        # ``order`` is not to be trusted); ``plain`` says every tuple of
        # ``order`` stands for one copy of its value (each count 1, nothing
        # dropped), so an extract reads the values off the tuples
        self.order: list[tuple] | None = None
        self.fresh: list[list] | None = None
        self.plain = False

    def __getstate__(self):
        # the layout snapshots have always had, so old ones load and new ones
        # are no larger
        return None, {"items": self.items, "total": self.total}

    def __setstate__(self, state):
        self.items, self.total = state[1]["items"], state[1]["total"]
        self.order = self.fresh = None
        self.plain = False


#: exact scalar types whose equality (with the type) implies equal canonical
#: bytes, so one encoding serves every equal value of a block; containers are
#: left out: (1, 2) == (1.0, 2) but they encode apart
_MEMO_TYPES = frozenset(
    {int, float, str, bytes, bool, type(None)}
    | {t for t in np.sctypeDict.values() if np.dtype(t).kind in "biufMmUS"}
)


def _encode_block(values: list[tuple]) -> list[bytes]:
    """``_canonical_bytes`` of every value tuple, computed once per distinct
    tuple of plain scalars."""
    memo: dict[tuple, bytes] = {}
    out = []
    for v in values:
        types = tuple(map(type, v))
        if _MEMO_TYPES.issuperset(types):
            key = (types, v)
            ck = memo.get(key)
            if ck is None:
                ck = memo[key] = _canonical_bytes(v)
        else:
            ck = _canonical_bytes(v)
        out.append(ck)
    return out


class MultisetReducer(ReducerImpl):
    """Base for reducers re-extracted from a value multiset."""

    #: extracts in (sort key, arrival) order and keeps that order between
    #: extracts: the fold notes the entries it creates in ``state.fresh``
    ordered = False

    def make(self):
        return _MultisetState()

    def block_rows(self, arrays, diffs):
        # list(arr) keeps numpy scalars, as arr[i] gives them
        values = list(zip(*(list(arr) for arr in arrays)))
        return values, _encode_block(values), diffs.tolist()

    def fold_rows(self, state: _MultisetState, block, rows, time, seq):
        # row by row, because the order of arrival is part of the state: an
        # entry that empties and refills within a tick takes the refilling
        # row's (time, seq) and moves to the end of ``items``
        values, cks, diffs = block
        items = state.items
        # the one place entries are created, counted and dropped. The kept
        # order is told of an entry created and that an older one's count
        # moved (a dropped one's stays 0 for good: a refill is a new entry)
        fresh = None
        if self.ordered:
            fresh = state.fresh
            if fresh is None:
                fresh = state.fresh = []
        total = 0
        for k, i in enumerate(rows):
            ck, diff = cks[i], diffs[i]
            ent = items.get(ck)
            if ent is None:
                ent = items[ck] = [values[i], 0, (time, seq + k)]
                if fresh is not None:
                    fresh.append(ent)
            else:
                state.plain = False
            ent[1] += diff
            if ent[1] == 0:
                del items[ck]
            total += diff
        state.total += total
        return state


class MinReducer(MultisetReducer):
    def extract(self, state):
        return min(e[0][0] for e in state.items.values())


class MaxReducer(MultisetReducer):
    def extract(self, state):
        return max(e[0][0] for e in state.items.values())


class ArgMinReducer(MultisetReducer):
    """values = (cmp_value, id); ties broken by smallest key for determinism."""

    def extract(self, state):
        return min((e[0][0], e[0][1]) for e in state.items.values())[1]


class ArgMaxReducer(MultisetReducer):
    def extract(self, state):
        best = None
        for e in state.items.values():
            cand = (e[0][0], e[0][1])
            # max by value, min by id on ties
            if best is None or cand[0] > best[0] or (cand[0] == best[0] and cand[1] < best[1]):
                best = cand
        return best[1]


class UniqueReducer(MultisetReducer):
    def extract(self, state):
        if len(state.items) != 1:
            from pathway_tpu.internals.errors import report_error

            return report_error(
                "unique reducer: group holds more than one distinct value"
            )
        return next(iter(state.items.values()))[0][0]


class AnyReducer(MultisetReducer):
    def extract(self, state):
        # deterministic: smallest canonical encoding
        ck = min(state.items.keys())
        return state.items[ck][0][0]


class OrderedMultisetReducer(MultisetReducer):
    """Base of the reducers that extract in (sort key, arrival ``(time, seq)``)
    order. The sorted order is kept in ``state.order`` from one extract to the
    next: the first extract of a state sorts all its entries; a later one
    sorts the entries the fold created since (``state.fresh``) and merges that
    run in. A state with no order (restored, migrated) or folded by code that
    did not track takes the full sort again. The entries are visited for
    their counts only after a fold moved the count of one that was there
    (``state.plain``)."""

    ordered = True
    #: values are (value, sort_key) pairs; False orders by arrival alone
    with_sort_key = True
    skip_nones = False

    def _sorted_run(self, entries) -> list[tuple]:
        """One sort tuple per live entry, ending in its value and the entry,
        sorted. (time, seq) is unique in a state, so comparing two never
        reaches the values."""
        if self.with_sort_key:
            run = [(e[0][1], e[2], e[0][0], e) for e in entries if e[1]]
        else:
            run = [(e[2], e[0][0], e) for e in entries if e[1]]
        run.sort()
        return run

    def _pack(self, values: list) -> Any:
        """The reducer's value from the group's values in extract order."""
        raise NotImplementedError

    def extract(self, state):
        order, fresh = state.order, state.fresh
        state.fresh = None
        tok = None
        if order is None or fresh is None:
            # the full sort; a state's first extract here is that and nothing
            # else, a rebuild (the fold before it tracked nothing) says so
            if order is not None:
                tok = _obs.begin("reduce/order{sort}")
            order = state.order = self._sorted_run(state.items.values())
            state.plain = False
            attrs = {"pathway.entries": len(order)}
        else:
            tok = _obs.begin("reduce/order{merge}")
            run = self._sorted_run(fresh)
            if state.plain:
                state.plain = all(e[1] == 1 for e in fresh)
            elif len(order) + len(run) > len(state.items):
                # entries were dropped since the order was made: they leave it
                # (the lengths only say whether the pass is worth making: a
                # dropped entry's count is 0 and takes no place in the value)
                order = state.order = [t for t in order if t[-1][1]]
            # two sorted runs: Timsort finds them and merges in one pass
            order.extend(run)
            order.sort()
            attrs = {"pathway.entries": len(order), "pathway.fresh": len(run)}
        if state.plain:
            # no entry is visited: in sort order they lie scattered through
            # memory, each count four loads deep
            values = [t[-2] for t in order]
        else:
            values = []
            plain = True
            for t in order:
                count = t[-1][1]
                if count == 1:
                    values.append(t[-2])
                else:  # duplicates; a negative count takes no place
                    plain = False
                    values.extend([t[-2]] * max(count, 0))
            state.plain = plain
        if self.skip_nones:
            values = [v for v in values if v is not None]
        out = self._pack(values)
        if tok is not None:
            _obs.end(tok, attrs)
        return out


class TupleReducer(OrderedMultisetReducer):
    """Collect values; ordered by arrival (time, seq) for stability. With
    ``sort_by`` values are (value, sort_key) pairs ordered by sort_key. The
    order is kept between extracts (``OrderedMultisetReducer``)."""

    def __init__(self, skip_nones: bool = False, with_sort_key: bool = False):
        self.skip_nones = skip_nones
        self.with_sort_key = with_sort_key

    def _pack(self, values):
        return tuple(values)


class SortedTupleReducer(MultisetReducer):
    def __init__(self, skip_nones: bool = False):
        self.skip_nones = skip_nones

    def extract(self, state):
        vals = []
        for e in state.items.values():
            v = e[0][0]
            if self.skip_nones and v is None:
                continue
            vals.extend([v] * max(e[1], 0))
        return tuple(sorted(vals))


class NdarrayReducer(OrderedMultisetReducer):
    """values = (value, sort_key); returns np.ndarray sorted by sort_key, the
    order kept between extracts (``OrderedMultisetReducer``)."""

    def _pack(self, values):
        return np.asarray(values)


class EarliestReducer(MultisetReducer):
    def extract(self, state):
        return min(state.items.values(), key=lambda e: e[2])[0][0]


class LatestReducer(MultisetReducer):
    def extract(self, state):
        return max(state.items.values(), key=lambda e: e[2])[0][0]


class StatefulReducer(ReducerImpl):
    """``stateful_single/many`` — append-only fold with a user combine fn
    (reference: ``Reducer::Stateful`` + ``custom_reducers.py``)."""

    def __init__(self, combine_fn: Callable, many: bool = False):
        self.combine_fn = combine_fn
        self.many = many

    def make(self):
        return None

    def update(self, state, values, diff, time, seq):
        if diff < 0:
            raise RuntimeError("stateful reducers don't support retractions")
        if self.many:
            return self.combine_fn(state, [(*values, diff)])
        return self.combine_fn(state, *values)

    def extract(self, state):
        return state


class CustomAccumulatorReducer(ReducerImpl):
    """``pw.reducers.udf_reducer`` over a BaseCustomAccumulator subclass
    (reference: ``internals/custom_reducers.py``)."""

    def __init__(self, acc_cls):
        self.acc_cls = acc_cls

    def make(self):
        return None

    def update(self, state, values, diff, time, seq):
        neutral = self.acc_cls.from_row(list(values))
        if diff > 0:
            return neutral if state is None else state.update(neutral) or state
        if state is None:
            raise RuntimeError("retraction before any accumulation")
        if not hasattr(state, "retract"):
            raise RuntimeError(f"{self.acc_cls.__name__} does not support retractions")
        state.retract(neutral)
        return state

    def extract(self, state):
        return state.compute_result()
