"""Seconds the program spent restoring its saved state at boot: the engine's
resume file and the alerts' and sink's buffers (StageClock state_restore,
taken over the set-up by modes/restart.py, since no restore falls in the
window); None where nothing was restored, or the program keeps no such
clock."""


def read(run):
    st = run["stages"].get("state_restore")
    if not st or st.get("events", 0) <= 0:
        return None
    return st["busy_s"]
