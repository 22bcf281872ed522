"""The contract's last line, and the device it names."""

import json


def device_info(n_chips: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    peaks = [((dev.memory_stats() or {}).get("peak_bytes_in_use") or 0)
             for dev in devs[:n_chips]]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


def last_line(correct: bool, attempted: int, failed: int, metrics: dict,
              units: dict, device: dict, breakdown=None) -> str:
    doc = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {k: {"value": float(v), "unit": units[k]}
                    for k, v in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        doc["breakdown"] = breakdown
    return json.dumps(doc)
