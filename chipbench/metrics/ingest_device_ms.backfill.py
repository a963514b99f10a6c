"""Device milliseconds of the ingest program per ingest dispatch in the traced
window (jit_engine_ingest module time over the program's ingest_dispatch spans)."""

from chipbench.harness.program_spans import ingest_device_ms, spans_for


def read(run):
    spans = spans_for(run)
    return ingest_device_ms(run["trace"], spans) if spans else None
