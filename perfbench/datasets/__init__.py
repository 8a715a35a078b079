"""One module per dataset, found by the name a configuration gives.

A dataset module has: TABLE, SCHEMA [(column, type, role)], TEMPLATES
{name: refeval.Template}, `vocabs(config)` {coded column: sorted vocabulary},
and `segment(seed, index, n_rows, config)` -> {column: refeval.Column}.
It imports numpy, perfbench.refeval and the rules of `_dbgen.py` only, never the program.
"""
