"""The benchmark's own code: traffic, load, life-cycle, readers, reduction."""
import json
import os


def json_dir(path: str) -> list:
    """[(file name, parsed object)] of the .json data files in a directory,
    by name: configurations, traffic, metrics, programs and peaks are data."""
    out = []
    for fn in sorted(os.listdir(path)):
        if fn.endswith(".json"):
            with open(os.path.join(path, fn)) as f:
                out.append((fn, json.load(f)))
    return out
