"""Adding a cell as files and entries only.

An overlay is a directory shaped like a checkout: files under the
benchmark's directory, and ``BENCHMARK.add.json`` whose lists (``configs``,
``workloads``, ``end_to_end``, ``per_layer``) are appended to the
manifest's.  ``apply_overlay`` builds a checkout of the benchmark in a
new directory with the overlay laid over it, and REFUSES an overlay file
that would replace a file that is there: what it proves is that the
harness takes a new configuration, cell and per-layer metric with no edit
to anything that exists.
"""
import json
import os
import shutil

ADD = "BENCHMARK.add.json"
_LISTS = ("configs", "workloads", "end_to_end", "per_layer")


def apply_overlay(root, overlay_dir, dst):
    """A copy of ``root``'s benchmark (manifest + ``paths``) in ``dst``
    with ``overlay_dir`` laid over it; returns ``dst``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for path in manifest["paths"]:
        shutil.copytree(os.path.join(root, path), os.path.join(dst, path),
                        ignore=shutil.ignore_patterns("__pycache__"))
    overlay_dir = os.path.abspath(overlay_dir)
    for base, _, files in os.walk(overlay_dir):
        for name in files:
            src = os.path.join(base, name)
            rel = os.path.relpath(src, overlay_dir)
            if rel == ADD:
                continue
            out = os.path.join(dst, rel)
            if os.path.exists(out):
                raise FileExistsError(
                    f"overlay would replace {rel}: a later PR may add "
                    f"files, not edit one that is there")
            os.makedirs(os.path.dirname(out), exist_ok=True)
            shutil.copy(src, out)
    with open(os.path.join(overlay_dir, ADD)) as f:
        add = json.load(f)
    for key in _LISTS:
        manifest[key] = manifest[key] + add.get(key, [])
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return dst
