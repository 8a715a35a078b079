"""The lookUp join's bytes a second over the chip's HBM peak.

The work is counted here, from the query and the configuration alone, so that
it is the same whatever implements the join: for each distinct lookUp of a
query's template (dimension table, destination, foreign key) a launch reads
its segment's foreign-key codes once and writes a destination code a row
(4 B each), and reads the destination of every row of the dimension table
(4 B) once.

The time is the device time of the programs that hold the gather. The answers
say which those are: a program's `deviceWork` names the registered kernels
traced into it, and the gather of `kernels._lookup_codes` is registered as
`query.lookup_gather` (PR 41); the trace's "XLA Modules" line has each
program's launches and seconds under the same name (`jit_seg_groupby_<hash>`).
The gather's own op cannot be told by its name (XLA names such a fusion
generically: `grouped_double_hbm_share`), so the share is a floor, of the
whole launch: the filter's compares and the group-by kernel run in the same
seconds. A program without the registered name (any before PR 41), a trace
without such a program's launches, or a configuration without declared
tables gives nothing to read.
"""

import importlib
import re

from perfbench.layer_metrics._spans import PEAKS, program_of

LAYER = "device: fused per-segment program (query/kernels.py)"
UNIT = "%"
MOVES = "query_p50_ms"
SOURCE = "device_trace"
NEEDS_TRACE = True

GATHER = "query.lookup_gather"  # the registered name of the gather in a program's `deviceWork`
_LOOKUP = re.compile(r"lookup\(\s*'([^']+)'\s*,\s*'([^']+)'\s*,\s*'[^']+'\s*,\s*([A-Za-z_][A-Za-z0-9_]*)\s*\)", re.IGNORECASE)


def bytes_of_a_launch(sql: str, config: dict) -> float:
    """What the joins of one launched segment of a query must move at least."""
    dim_rows = {t["name"]: t["rows"] for t in config.get("tables", []) if "rows" in t}
    lookups = {m.groups() for m in _LOOKUP.finditer(sql)}
    return float(sum(config["segmentRows"] * 8.0 + dim_rows[table] * 4.0 for table, _, _ in lookups if table in dim_rows))


def bytes_per_launch(run) -> dict[str, float]:
    """Per program that holds the gather: the bytes of one of its launches, a
    mean over the launches the window's answers report (each answer's by its
    own template)."""
    try:
        templates = importlib.import_module(f"perfbench.datasets.{run['config']['dataset']}").TEMPLATES
    except (ImportError, KeyError, AttributeError):
        return {}
    moved: dict[str, float] = {}
    launches: dict[str, int] = {}
    for s in run["good"]:
        work = s.doc.get("deviceWork") if isinstance(s.doc, dict) else None
        template = templates.get(getattr(s, "template", None))
        if template is None:
            continue
        for program, w in (work if isinstance(work, dict) else {}).items():
            n = int(w.get("launches", 0))
            if n > 0 and float(w.get("kernels", {}).get(GATHER, {}).get("calls", 0)) > 0:
                moved[program] = moved.get(program, 0.0) + n * bytes_of_a_launch(template.sql, run["config"])
                launches[program] = launches.get(program, 0) + n
    return {p: moved[p] / launches[p] for p in moved}


def read(run):
    t, config = run["trace"], run["config"]
    if t is None or len(PEAKS) != 1 or not config.get("tables"):
        return None
    per_launch = bytes_per_launch(run)
    moved = seconds = 0.0
    for name, sec, n in t["modules"]:
        one = per_launch.get(program_of(name) or "")
        if one:
            moved += n * one
            seconds += sec
    if moved <= 0 or seconds <= 0:
        return None
    return 100.0 * moved / seconds / next(iter(PEAKS.values()))["hbm_bytes_per_s"]
