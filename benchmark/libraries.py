"""A configuration's pattern library as a directory of YAML files.

``directory`` libraries ship beside their configuration file; ``synth``
libraries are generated from the configuration's numbers into a fixed
directory inside the checkout (written once, rewritten only when the
configuration changes). The harness serves the directory; the reference
reads the same files.
"""

from __future__ import annotations

import hashlib
import json
import os

from benchmark.cell import ROOT


def _synth_fields(lib: dict, i: int) -> dict:
    svc = lib["services"][i % len(lib["services"])]
    err = lib["errors"][(i // len(lib["services"])) % len(lib["errors"])]
    body = lib["body_format"].format(svc=svc, i=i)
    return {"svc": svc, "err": err, "err_lower": err.lower(), "body": body}


def synth_pattern_set(lib: dict) -> dict:
    """``bench_bank.synth_library``'s shapes, as numbers in the config."""
    conf = lib["confidence"]
    sec = lib.get("secondary")
    patterns = []
    for i in range(int(lib["patterns"])):
        f = _synth_fields(lib, i)
        shape = lib["shapes"][i % len(lib["shapes"])]
        p = {
            "id": lib["id_format"].format(i=i),
            "name": lib["name_format"].format(i=i),
            "severity": lib["severities"][i % len(lib["severities"])],
            "primary_pattern": {
                "regex": shape["regex"].format(**f),
                "confidence": conf["base"] + (i % conf["period"]) * conf["step"],
            },
        }
        if sec and i % sec["every"] == 0:
            p["secondary_patterns"] = [{
                "regex": sec["regex"].format(**f),
                "weight": sec["weight"],
                "proximity_window": sec["proximity_window"],
            }]
        patterns.append(p)
    return {
        "metadata": {"library_id": lib["library_id"], "name": "synthetic"},
        "patterns": patterns,
    }


def synth_hit_line(lib: dict, i: int, num: int) -> str:
    """A log line that pattern ``i`` matches, in its own shape."""
    shape = lib["shapes"][i % len(lib["shapes"])]
    return shape["hit"].format(num=num, **_synth_fields(lib, i))


def library_dir(config: dict, root: str = ROOT) -> str:
    """The directory to serve for ``config``, generated if need be."""
    lib = config["library"]
    if lib["kind"] == "directory":
        return os.path.join(root, lib["path"])
    if lib["kind"] != "synth":
        raise ValueError(f"unknown library kind {lib['kind']!r}")
    stamp = hashlib.sha256(
        json.dumps(lib, sort_keys=True).encode()
    ).hexdigest()
    out = os.path.join(root, ".cache", "bench", "libraries", config["name"])
    stamp_path = os.path.join(out, ".stamp")
    try:
        with open(stamp_path, encoding="utf-8") as f:
            if f.read().strip() == stamp:
                return out
    except FileNotFoundError:
        pass
    os.makedirs(out, exist_ok=True)
    # JSON is YAML: one flow document, written and read fast
    tmp = os.path.join(out, "library.yaml.tmp")
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(synth_pattern_set(lib), f)
    os.replace(tmp, os.path.join(out, "library.yaml"))
    with open(stamp_path, "w", encoding="utf-8") as f:
        f.write(stamp + "\n")
    return out
