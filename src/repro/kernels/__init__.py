# Pallas kernels of the store's device path: delta_overlay folds node
# payload deltas for snapshot reads (core/snapshot.py, core/tgi.py);
# temporal_motif counts per-node triangles for fused analytics
# (taf/compile.py). Each is <name>.py + ops.py + ref.py.
