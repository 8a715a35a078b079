"""`ssb_flat` for `ssb-flat-4srv`: the same table, generator, templates and
reference specs, and a refusal of a program that cannot load the table inside a run.

A controller that decodes an uploaded segment and encodes it again (every
commit before PR 27) takes a 4M-row segment every 7.1-7.7 s whatever feeds it
(PERF.md section 5): 32 segments are 230-250 s of loading, the run passes the
360 s a run may take and is killed, with no line and no exit code of its own.
A cell that a program cannot run has to fail at once, so this module looks,
where the launcher imports it and before any role starts, for the entry that
lands an upload as sent. It reads the program's source and imports nothing of
it: the launcher imports neither jax nor pinot_tpu.
"""

from __future__ import annotations

from perfbench.datasets.ssb_flat import *  # noqa: F401,F403  the dataset is ssb_flat's, name for name
from perfbench.manifest import ROOT

#: `Controller.upload_segment_archive`, which `POST /segments/<table>` ends in since PR 27
LANDS_UPLOADS = ("pinot_tpu/cluster/controller.py", "def upload_segment_archive(")


def require_a_program_that_lands_uploads(root=ROOT) -> None:
    path, entry = LANDS_UPLOADS
    if entry not in (root / path).read_text():
        raise SystemExit(
            f"ssb-flat-4srv: {path} has no `{entry.removeprefix('def ').rstrip('(')}`: this program's controller decodes and "
            "re-encodes every uploaded segment (about 7 s each), and 32 segments do not load inside a run's 360 s"
        )


require_a_program_that_lands_uploads()
