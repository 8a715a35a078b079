"""TSBS `devops` / `cpu-only`: the `cpu` table and the three `double-groupby`
query types.

Source: the Time Series Benchmark Suite (github.com/timescale/tsbs), use case
`devops`, `cpu-only`: one row a host every 10 s with a timestamp, the ten host
tags and the ten CPU metrics, each metric a random walk with N(0,1) steps
clamped to [0, 100] that starts at U(0,100); query types `double-groupby-1`,
`-5`, `-all`: the mean of 1, 5 or all 10 metrics for every host and every
hour of a random 12-hour window, ordered by hour and host. What is assumed,
not the source's: see the configuration file.

Rows are in time order, all hosts of one instant together, and a segment is a
stretch of whole instants, so segments do not overlap in time and a query's
window rejects most of them by their metadata alone.

One thing here is for the reference only. `segment()` returns one column
more than `SCHEMA` names: `hour`, the start of each row's hour as a code into
the table's hours (`vocabs()` has it too). `datagen.build_segment` builds the
schema's columns and never sees it; `refeval` groups by it where the program
groups by `DATETRUNC('hour', ts)`.

A `double-groupby-1` or `-5` draws which 1 or 5 of the ten metrics it averages
(`double-groupby-all` takes all ten), so a window's queries differ in their
columns as well as in their hours. `refeval` hands an aggregate's value
function the columns alone, not the draw, so `_where`, which gets both and
runs first, sets the drawn metrics beside the table's columns as `drawn0`,
`drawn1`, ...: the aggregates read those.
"""

from __future__ import annotations

import functools

import numpy as np

from perfbench.refeval import Column, Spec, Template

TABLE = "cpu"

TAGS = ["hostname", "region", "datacenter", "rack", "os", "arch", "team", "service", "service_version", "service_environment"]
METRICS = ["usage_user", "usage_system", "usage_idle", "usage_nice", "usage_iowait", "usage_irq", "usage_softirq",
           "usage_steal", "usage_guest", "usage_guest_nice"]  # fmt: skip
SCHEMA = [("ts", "LONG", "dimension")] + [(t, "STRING", "dimension") for t in TAGS] + [(m, "DOUBLE", "metric") for m in METRICS]

START_MS = 1_451_606_400_000  # 2016-01-01T00:00:00Z, TSBS's default --timestamp-start
HOUR_MS = 3_600_000
TABLE_HOURS = 48  # what the draws leave room in; the configuration's `days` has to agree
WINDOW_HOURS = 12  # TSBS's DoubleGroupByDuration

# TSBS's host tags (devops/host.go): a region and one of its datacenters, a rack of 100,
# an OS, an architecture, a team, a service of 20, a service version of 2, an environment
REGIONS = {
    "us-east-1": ["us-east-1a", "us-east-1b", "us-east-1c", "us-east-1e"],
    "us-west-1": ["us-west-1a", "us-west-1b"],
    "us-west-2": ["us-west-2a", "us-west-2b", "us-west-2c"],
    "eu-west-1": ["eu-west-1a", "eu-west-1b", "eu-west-1c"],
    "eu-central-1": ["eu-central-1a", "eu-central-1b"],
    "ap-southeast-1": ["ap-southeast-1a", "ap-southeast-1b"],
    "ap-southeast-2": ["ap-southeast-2a", "ap-southeast-2b"],
    "ap-northeast-1": ["ap-northeast-1a", "ap-northeast-1c"],
    "sa-east-1": ["sa-east-1a", "sa-east-1b", "sa-east-1c"],
}
CHOICES = {
    "rack": [str(i) for i in range(100)],
    "os": ["Ubuntu16.10", "Ubuntu16.04LTS", "Ubuntu15.10"],
    "arch": ["x64", "x86"],
    "team": ["SF", "NYC", "LON", "CHI"],
    "service": [str(i) for i in range(20)],
    "service_version": ["0", "1"],
    "service_environment": ["production", "staging", "test"],
}

def steps(config: dict) -> int:
    """10 s instants in the table: it spans the configuration's `days`, whatever its hosts and segments."""
    total = int(config["days"]) * 86_400 // int(config["intervalSeconds"])
    if int(config["days"]) * 24 != TABLE_HOURS or config["rows"] != total * config["hosts"] or config["segmentRows"] % config["hosts"]:
        raise ValueError(f"rows {config['rows']} and segmentRows {config['segmentRows']} are not whole instants of {config['hosts']} hosts over {TABLE_HOURS} hours")
    return total


@functools.lru_cache(maxsize=4)
def _host_tags(seed: int, hosts: int) -> dict[str, np.ndarray]:
    """Each host's tags, the same in every segment: codes into the sorted vocabularies."""
    rng = np.random.default_rng([seed, 999_983])
    names = np.array([f"host_{i}" for i in range(hosts)])
    region = rng.integers(0, len(REGIONS), hosts)
    centers = _vocab("datacenter")
    datacenter = np.array([np.searchsorted(centers, rng.choice(REGIONS[_vocab("region")[r]])) for r in region])
    tags = {"hostname": np.argsort(np.argsort(names)), "region": region, "datacenter": datacenter}
    for tag in CHOICES:
        tags[tag] = rng.integers(0, len(CHOICES[tag]), hosts)
    return {k: v.astype(np.int32) for k, v in tags.items()}


@functools.lru_cache(maxsize=None)
def _vocab(tag: str) -> np.ndarray:
    if tag == "region":
        return np.array(sorted(REGIONS))
    if tag == "datacenter":
        return np.array(sorted(dc for dcs in REGIONS.values() for dc in dcs))
    return np.array(sorted(CHOICES[tag]))


def vocabs(config: dict) -> dict[str, np.ndarray]:
    n = steps(config)
    out = {tag: _vocab(tag) for tag in TAGS[1:]}
    out["hostname"] = np.array(sorted(f"host_{i}" for i in range(config["hosts"])))
    out["ts"] = START_MS + np.arange(n, dtype=np.int64) * (int(config["intervalSeconds"]) * 1000)
    out["hour"] = START_MS + np.arange(TABLE_HOURS, dtype=np.int64) * HOUR_MS
    return out


def segment(seed: int, index: int, n: int, config: dict) -> dict[str, Column]:
    """Segment `index`: `n / hosts` consecutive instants of every host, in time order."""
    hosts, voc = int(config["hosts"]), vocabs(config)
    per = n // hosts
    first = index * per
    instant = np.repeat(np.arange(first, first + per, dtype=np.int32), hosts)
    tags = _host_tags(seed, hosts)
    cols = {"ts": Column(instant, voc["ts"])}
    for tag in TAGS:
        cols[tag] = Column(np.tile(tags[tag], per), voc[tag])
    # ten walks a host: U(0,100) at the segment's first instant, then N(0,1) steps, clamped (TSBS's ClampedRandomWalk)
    rng = np.random.default_rng([seed, index])
    walk = np.empty((per, hosts, len(METRICS)))
    walk[0] = rng.uniform(0.0, 100.0, (hosts, len(METRICS)))
    moves = rng.standard_normal((per - 1, hosts, len(METRICS)))
    for t in range(1, per):
        np.clip(walk[t - 1] + moves[t - 1], 0.0, 100.0, out=walk[t])
    for j, metric in enumerate(METRICS):
        cols[metric] = Column(np.ascontiguousarray(walk[:, :, j]).reshape(-1))
    hour = (voc["ts"][instant] - START_MS) // HOUR_MS
    cols["hour"] = Column(hour.astype(np.int32), voc["hour"])  # the reference's group key; no column of the table
    return cols


# ---------------------------------------------------------------------------
# double-groupby-1, -5, -all
# ---------------------------------------------------------------------------


def _draw(k: int):
    """A 12-hour window that starts on the 10 s grid anywhere the table leaves
    room, and which `k` of the ten metrics to average (all ten: in the table's order)."""

    def draw(rng):
        lo = START_MS + int(rng.integers(0, (TABLE_HOURS - WINDOW_HOURS) * 360 + 1)) * 10_000
        drawn = METRICS if k == len(METRICS) else [METRICS[i] for i in rng.choice(len(METRICS), k, replace=False)]
        return {"lo": lo, "hi": lo + WINDOW_HOURS * HOUR_MS, "metrics": drawn, "avgs": ", ".join(f"AVG({m})" for m in drawn)}

    return draw


def _where(cols, p):
    for i, m in enumerate(p["metrics"]):
        cols[f"drawn{i}"] = cols[m]  # for the aggregates, which are not told the draw (see the module's text)
    ts = cols["ts"]
    a, b = np.searchsorted(ts.vocab, [p["lo"], p["hi"]], "left")
    return (ts.codes >= a) & (ts.codes < b)


def _template(k: int) -> Template:
    """The mean of `k` drawn metrics by host and hour."""
    return Template(
        "SELECT hostname, DATETRUNC('hour', ts), {avgs} FROM cpu WHERE ts >= {lo} AND ts < {hi} "
        "GROUP BY hostname, DATETRUNC('hour', ts) ORDER BY DATETRUNC('hour', ts), hostname LIMIT 60000",
        _draw(k),
        Spec(
            _where,
            keys=["hostname", "hour"],
            aggs=[("avg", lambda cols, i=i: cols[f"drawn{i}"].codes) for i in range(k)],
            select=["hostname", "hour"] + [f"agg{i}" for i in range(k)],
            order=[("hour", False), ("hostname", False)],
            exact=False,
        ),
    )


TEMPLATES = {"double-groupby-1": _template(1), "double-groupby-5": _template(5), "double-groupby-all": _template(len(METRICS))}
