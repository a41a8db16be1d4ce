#!/usr/bin/env python3
"""How widely a cell's runs spread, and the bound that spread asks for.

    python3 benchmarks/chip/spread.py SET_DIR ...
    python3 benchmarks/chip/spread.py SET_DIR ... --reported M=SPREAD/MEDIAN

Each ``SET_DIR`` holds one set of runs, one file a run (``*.out``): the
standard output and error of ``run.py`` together (``> run.out 2>&1``).  A
run's result is the last JSON line of its file; its cell and seed come from
the ``[bench] <cell> seed <n>:`` line it logs.  Only run output is read:
nothing here touches the chip or the program.  Sets made in one call come
in pairs on the same seeds, as the check makes them.

For each cell and metric, set by set: the median; the interquartile spread
(the distance between the quartiles of ``statistics.quantiles(n=4)``, over
the median) of all runs; and the check's spread, the interquartile distance
of the runs without the one farthest from their median, where that narrows
it.  The check refuses a bound as too tight where the mean of its two sets'
spreads passes half of it, and as too loose where it passes eight times the
wider untrimmed spread of its sets.  ``--reported`` adds spreads the check
itself reported for a metric (in its unit, over its median).  Then, over
the sets, the bound the rule gives, a step of ``STEPS``:

* at least twice the widest check's spread, of the sets or reported, so
  that every set stays under half the bound; and at least 1%;
* as near as the steps allow to the larger of five times the widest
  untrimmed spread of the sets and three times the widest reported one;
* not over eight times the narrowest pair's wider untrimmed spread, the
  least that the check's looseness test may read.

Above 0.25 the bound stays at 0.25, a coarse guard.  ``setup_s`` leaves out
each set's first run (it compiles) and keeps its fixed bound of 0.25.
"""

from __future__ import annotations

import argparse
import json
import re
import statistics
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

STEPS = (0.01, 0.015, 0.02, 0.025, 0.03, 0.035, 0.04, 0.05, 0.075, 0.10,
         0.15, 0.20, 0.25)
SETUP_BOUND = 0.25
_LOGGED = re.compile(r"\[bench\] (\S+) seed (-?\d+):")


def _iqr(values: Sequence[float]) -> float:
    """The distance between the first and the third quartile of
    ``statistics.quantiles(n=4)``; 0 for fewer than two values."""
    if len(values) < 2:
        return 0.0
    q1, _, q3 = statistics.quantiles(values, n=4)
    return q3 - q1


def quartile_spread(values: Sequence[float]) -> float:
    """The interquartile distance over the median; 0 for fewer than two
    values."""
    if len(values) < 2:
        return 0.0
    return _iqr(values) / statistics.median(values)


def trimmed(values: Sequence[float]) -> List[float]:
    """The values without the one farthest from their median."""
    if len(values) < 3:
        return list(values)
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return [v for i, v in enumerate(values) if i != far]


def check_spread(values: Sequence[float]) -> float:
    """The check's spread over the median of all runs: the interquartile
    distance without the run farthest from the median, where that narrows
    it."""
    if len(values) < 2:
        return 0.0
    return min(_iqr(values), _iqr(trimmed(values))) / statistics.median(values)


def verdict(spread: float, median: float, bound: float) -> str:
    """What the check makes of a side whose runs spread by ``spread`` (in
    the metric's unit) around ``median``: ``unresolved`` past the bound,
    ``warned`` past half of it, else ``resolved``."""
    room = bound * median
    if spread > room:
        return "unresolved"
    return "warned" if spread > room / 2 else "resolved"


def bound_for(sets: Sequence[Sequence[float]], reported: Sequence[float] = ()
              ) -> Tuple[float, Dict[str, float]]:
    """The bound the rule gives for one cell's sets of one metric, sets
    made in pairs, and spreads the check reported (shares of its median);
    and what each part of the rule asked for."""
    spreads = [quartile_spread(s) for s in sets]
    pairs = [max(spreads[i:i + 2]) for i in range(0, len(spreads), 2)]
    widest = max([check_spread(s) for s in sets] + list(reported))
    asks = {
        "2 x check": 2 * widest,
        "floor": STEPS[0],
        "5 x spread": 5 * max(spreads),
        "3 x reported": 3 * max(reported, default=0.0),
        "8 x narrowest pair": 8 * min(pairs),
    }
    need = max(asks["2 x check"], asks["floor"])
    aim = max(asks["5 x spread"], asks["3 x reported"])
    fits = [s for s in STEPS if need <= s <= asks["8 x narrowest pair"]]
    if fits:
        return min(fits, key=lambda s: abs(s - aim)), asks
    return next((s for s in STEPS if s >= need), STEPS[-1]), asks


def read_run(path: Path) -> Optional[Tuple[str, int, dict]]:
    """(cell, seed, result) of one run's output; None where the file holds
    no result line or no cell."""
    cell, seed, result = None, None, None
    for line in path.read_text(errors="replace").splitlines():
        m = _LOGGED.search(line)
        if m:
            cell, seed = m.group(1), int(m.group(2))
        elif line.startswith("{"):
            try:
                result = json.loads(line)
            except json.JSONDecodeError:
                continue
    if cell is None or not isinstance(result, dict):
        return None
    return cell, seed, result


def collect(set_dirs: Sequence[Path]):
    """{cell: {metric: [(set name, [(seed, value), ...]), ...]}} in the
    order of the sets and of the files' names, and the runs not correct."""
    table: Dict[str, Dict[str, List]] = {}
    wrong = []
    for d in set_dirs:
        for f in sorted(Path(d).glob("*.out")):
            got = read_run(f)
            if got is None:
                wrong.append(f"{f}: no result")
                continue
            cell, seed, result = got
            if result.get("correct") is not True:
                wrong.append(f"{f}: correct={result.get('correct')!r}")
            for name, m in result.get("metrics", {}).items():
                sets = table.setdefault(cell, {}).setdefault(name, [])
                if not sets or sets[-1][0] != str(d):
                    sets.append((str(d), []))
                sets[-1][1].append((seed, float(m["value"])))
    return table, wrong


def report(table, bounds: Dict[str, float],
           reported: Dict[str, List[float]]) -> None:
    for cell, metrics in table.items():
        for name, sets in metrics.items():
            print(f"{cell} {name}")
            now = bounds.get(name)
            values = []
            for set_name, runs in sets:
                if name == "setup_s":
                    runs = runs[1:]
                v = [x for _, x in runs]
                if not v:
                    continue
                values.append(v)
                med = statistics.median(v)
                at_bound = "" if now is None else "; at the bound now " + \
                    verdict(check_spread(v) * med, med, now)
                print(f"  {set_name}: n {len(v)}, median {med!r}, spread "
                      f"{quartile_spread(v):.5f}, check "
                      f"{check_spread(v):.5f}{at_bound}; seeds "
                      f"{[s for s, _ in runs]}; values {v}")
            if not values:
                continue
            pooled = [x for v in values for x in v]
            print(f"  all {len(pooled)}: spread "
                  f"{quartile_spread(pooled):.5f}, trimmed "
                  f"{quartile_spread(trimmed(pooled)):.5f}")
            if name == "setup_s":
                meds = [statistics.median(v) for v in values]
                print(f"  bound {SETUP_BOUND} (fixed); set medians {meds}")
                continue
            b, asks = bound_for(values, reported.get(name, ()))
            parts = ", ".join(f"{k} {v:.5f}" for k, v in asks.items())
            print(f"  bound by the rule {b} ({parts}); in BENCHMARK.json "
                  f"{now}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("sets", nargs="+", type=Path,
                    help="directories, one set of runs each")
    ap.add_argument("--reported", action="append", default=[],
                    metavar="METRIC=SPREAD/MEDIAN",
                    help="a spread the check reported for one of its sets")
    args = ap.parse_args(argv)
    bench = json.loads((Path(__file__).resolve().parents[2]
                        / "BENCHMARK.json").read_text())
    bounds = {m["name"]: m["bound"] for m in bench["end_to_end"]}
    reported: Dict[str, List[float]] = {}
    for r in args.reported:
        name, _, ratio = r.partition("=")
        spread_, _, median = ratio.partition("/")
        reported.setdefault(name, []).append(float(spread_) / float(median))
    table, wrong = collect(args.sets)
    report(table, bounds, reported)
    for w in wrong:
        print(f"not correct: {w}")
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
