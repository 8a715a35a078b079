"""Hand-written device kernels (Pallas). The engine reaches them through
`pinot_tpu.ops.groupby_pallas`; nothing is re-exported here."""
