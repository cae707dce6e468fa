"""Model FLOPs of the layer-pattern hybrid's window steps (``bench/hybrid/
counts.py``) over the window's host-clock time, as a share of the chip's
bf16 peak. Nothing is read where the built model differs from what the
count assumes: its ``hybrid_layers`` gauge (layers of each kind) against
the configuration's pattern, or its ``ssd_chunks`` gauge (chunks a Mamba2
call scans) against the configuration's chunk; the driver copies both
into the record."""
from bench.hybrid import Dims, counts


def read(run):
    r = run.record
    m = Dims.from_config(run.cfg)
    layers = {k: m.layer_types.count(k) for k in set(m.layer_types)}
    chunks = {str(min(m.chunk, r.get("seq", 0))): -(-r.get("seq", 0) // m.chunk)}
    if not r.get("steps") or r.get("hybrid_layers") != layers \
            or ("mamba" in layers and r.get("ssd_chunks") != chunks):
        return None
    flops = counts.train_step_flops(m, r["batch"], r["seq"]) * r["steps"]
    return 100.0 * flops / r["window_s"] / run.peaks["bf16_flops_per_s"]
