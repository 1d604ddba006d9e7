"""Persisted, chip-keyed substitution policy derived from the codec probe.

Round 4 froze the default-substitution set (``PALLAS_DEFAULT_WINS``) from one
chip's probe data — and the probe itself showed how treacherous a frozen
constant is: ``int8_per_token`` read 2.12x in round 3 and 0.79x in round 4
once the interleaved-pair estimator removed phase drift. A different TPU
generation would silently inherit a stale policy.

This module closes that loop: every bench run's probe
(``tools/pallas_probe.probe_all``) records the measured
``roundtrip_speedup_vs_jnp`` per codec into a small JSON cache keyed by a
backend/chip fingerprint; ``pallas_variant(..., measured_wins_only=True)``
consults the cache for the CURRENT chip first and only falls back to the
frozen constant when no measurement exists for it. A fresh chip therefore
re-derives its winners on its first bench, and a codec that stops winning
stops being substituted on the next.

Cache location: ``EDGELLM_PROBE_CACHE`` or
``~/.cache/edgellm_tpu/pallas_wins.json``. Writes are atomic (tmp+rename);
corrupt or unreadable caches degrade to the no-data fallback, never an error.
"""
from __future__ import annotations

import json
import math
import os
import tempfile
from typing import Optional


def _cache_path() -> str:
    return os.environ.get(
        "EDGELLM_PROBE_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "edgellm_tpu",
                     "pallas_wins.json"))


def fingerprint() -> str:
    """Backend + device kind of the chip the current process would run on —
    the cache key that keeps one machine's measurements from steering
    another's policy (e.g. ``tpu:TPU v5 lite``)."""
    import jax

    try:
        kind = jax.devices()[0].device_kind
    except Exception:
        kind = "unknown"
    return f"{jax.default_backend()}:{kind}"


def base_name(codec_name: str) -> str:
    """Probe result names -> policy keys: the selective family probes as
    ``selective_int4_r<ratio>_<high>`` but is one substitution decision."""
    if codec_name.startswith("selective_int4"):
        return "selective_int4"
    return codec_name


def load_speedups(fp: Optional[str] = None) -> Optional[dict]:
    """``{base codec name: measured roundtrip speedup}`` for this chip, or
    None when the cache holds no data for it (callers fall back to the
    frozen ``PALLAS_DEFAULT_WINS``)."""
    try:
        with open(_cache_path()) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    entry = data.get(fp or fingerprint())
    if not isinstance(entry, dict):
        return None
    speedups = entry.get("speedups")
    if not isinstance(speedups, dict):
        return None
    out = {k: float(v) for k, v in speedups.items()
           if isinstance(v, (int, float)) and math.isfinite(v)}
    return out or None


def record(results, fp: Optional[str] = None) -> Optional[str]:
    """Merge one probe run's codec blocks (``probe_all()["codecs"]``) into
    the cache under this chip's fingerprint; returns the cache path written,
    or None when the results carry no finite speedups (e.g. parity-only
    probes on CPU). Unwritable locations are a no-op, not an error — the
    policy then simply stays on the fallback constant."""
    speedups = {}
    for r in results:
        # prefer the probe's unrounded ratio: WIN_MARGIN is a hysteresis
        # threshold and must never see a 1.045 reading pre-rounded to 1.05
        # (the rounded field stays for display and as back-compat fallback)
        s = r.get("roundtrip_speedup_vs_jnp_raw",
                  r.get("roundtrip_speedup_vs_jnp"))
        if isinstance(s, (int, float)) and math.isfinite(s):
            speedups[base_name(r["codec"])] = float(s)
    if not speedups:
        return None
    path = _cache_path()
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        try:
            with open(path) as f:
                data = json.load(f)
            if not isinstance(data, dict):
                data = {}
        except (OSError, ValueError):
            data = {}
        key = fp or fingerprint()
        entry = data.get(key) if isinstance(data.get(key), dict) else {}
        merged = dict(entry.get("speedups") or {})
        merged.update(speedups)
        data[key] = {"speedups": merged}
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                                   suffix=".tmp")
        with os.fdopen(fd, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        return path
    except OSError:
        return None


#: substitution requires the measured speedup to clear this margin, not just
#: 1.0: the interleaved-pair median still swings a few percent run to run
#: (the module docstring's r3/r4 flip), and a codec oscillating around
#: break-even must NOT flap into the default path on one 1.02x reading —
#: "earned" means measurably faster, at worst costing a true ~1.04x
#: marginal win (which the next probe can still promote)
WIN_MARGIN = 1.05


def measured_win(codec_name: str, fp: Optional[str] = None) -> Optional[bool]:
    """True/False when this chip has a measurement for the codec (win =
    speedup >= WIN_MARGIN), None when there is no data (caller falls back)."""
    speedups = load_speedups(fp)
    if speedups is None:
        return None
    s = speedups.get(base_name(codec_name))
    if s is None:
        return None
    return s >= WIN_MARGIN
