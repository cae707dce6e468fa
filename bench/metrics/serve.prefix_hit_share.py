"""Prompt chunks served from the prefix cache over all prompt chunks in the
window (the engine's own counters)."""


def read(run):
    c = run.record["counters"]
    total = c["prefill_chunks"] + c["prefill_chunks_skipped"]
    if total == 0:
        return None
    return 100.0 * c["prefill_chunks_skipped"] / total
