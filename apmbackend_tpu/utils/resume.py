"""JSON resume-file snapshots.

Role parity with the reference's per-module "resume files" (SURVEY.md §5.4):
JSON state written every N seconds and on shutdown, loaded on boot if present.
The reference needed a Map-aware replacer/reviver (util_methods.js:189-242);
here dicts serialize natively, but the wrapper shape
``{"dataType": "Map", "value": [[k, v], ...]}`` is still understood on load and
produced for dicts marked explicitly, keeping snapshots interchange-compatible.
"""

from __future__ import annotations

import json
import os
import tempfile
from typing import Any, Optional


def _sanitize(obj: Any) -> Any:
    """NaN/Inf floats -> None, matching JSON.stringify (which emits null);

    keeps snapshots loadable by strict parsers incl. the reference's JSON.parse."""
    if isinstance(obj, float) and (obj != obj or obj in (float("inf"), float("-inf"))):
        return None
    if isinstance(obj, dict):
        return {k: _sanitize(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_sanitize(v) for v in obj]
    return obj


def _revive(obj: Any) -> Any:
    if isinstance(obj, dict):
        if obj.get("dataType") == "Map" and isinstance(obj.get("value"), list):
            return {k: _revive(v) for k, v in obj["value"]}
        return {k: _revive(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_revive(v) for v in obj]
    return obj


def save_resume_file(path: str, obj: Any, *, logger=None, quiet: bool = True) -> None:
    if not quiet and logger:
        logger.info(f"Saving data to resume file: {path}")
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    from ..obs.attrib import STAGE_STATE_SAVE, get_attrib

    clock = get_attrib().clock(STAGE_STATE_SAVE, counters=("bytes",), shared=True)
    # Atomic write: the reference's writeFileSync can leave a torn file on
    # crash, which its loader then discards; we avoid the data loss instead.
    with clock.span():
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(os.path.abspath(path)), suffix=".tmp")
        try:
            with os.fdopen(fd, "w", encoding="utf-8") as fh:
                json.dump(_sanitize(obj), fh, allow_nan=False)
                clock.count("bytes", fh.tell())
            os.replace(tmp, path)
        except BaseException:
            if os.path.exists(tmp):
                os.unlink(tmp)
            raise
    if not quiet and logger:
        logger.info(f"Resume file has been saved: {path}")


def load_resume_file(path: str, *, logger=None) -> Optional[Any]:
    if not os.path.exists(path):
        if logger:
            logger.warning(f"Resume file does not exist, will not resume data: {path}")
        return None
    try:
        with open(path, "r", encoding="utf-8") as fh:
            return _revive(json.load(fh))
    except (ValueError, OSError):
        # ValueError covers JSONDecodeError AND UnicodeDecodeError: a torn
        # write can truncate mid-multibyte-sequence, which fails the utf-8
        # decode before the JSON parser ever runs — both mean "start fresh"
        if logger:
            logger.error(f"Could not parse JSON content from resume file: {path}")
        return None
