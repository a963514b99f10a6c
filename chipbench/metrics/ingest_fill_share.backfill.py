"""Percent of the ingest program's padded slots that carried a record, over the
window (StageClock ingest_dispatch counters records and slots)."""


def read(run):
    st = run["stages"].get("ingest_dispatch")
    if not st or st.get("slots", 0) <= 0:
        return None
    return 100.0 * st["records"] / st["slots"]
