"""``python -m transmogrifai_tpu.cli mesh`` — inspect the search mesh.

The operator's view of the sharded search (docs/distributed.md): which
devices are visible, what mesh the selector would resolve under the
current ``TX_SEARCH_MESH`` policy, and the knobs that change it::

    python -m transmogrifai_tpu.cli mesh [--format json]

Initializes the JAX backend (it enumerates devices).
"""
from __future__ import annotations

import json
import os

__all__ = ["add_mesh_parser", "run_mesh"]


def add_mesh_parser(sub) -> None:
    m = sub.add_parser(
        "mesh",
        help="show visible devices and the search mesh the selector "
             "resolves under TX_SEARCH_MESH")
    m.add_argument("--format", choices=["text", "json"], default="text",
                   help="output format (default: text)")


def run_mesh(args) -> int:
    import jax

    from ..parallel.cv import resolve_search_mesh
    devices = jax.devices()
    mesh = resolve_search_mesh("auto")
    info = {
        "platform": devices[0].platform,
        "visibleDevices": len(devices),
        "policy": os.environ.get("TX_SEARCH_MESH", "auto"),
        "dataShards": os.environ.get("TX_SEARCH_DATA_SHARDS", "1"),
        "searchMesh": (None if mesh is None else
                       {str(k): int(v) for k, v in mesh.shape.items()}),
    }
    if args.format == "json":
        print(json.dumps(info, indent=1))
        return 0
    print(f"platform: {info['platform']}  "
          f"visible devices: {info['visibleDevices']}")
    if mesh is None:
        print("search mesh: none (local single-device path) — "
              f"policy TX_SEARCH_MESH={info['policy']!r}")
    else:
        print(f"search mesh: {info['searchMesh']} — the fold x grid "
              f"candidate axis shards over 'models'")
    print("knobs: TX_SEARCH_MESH=auto|off|<n devices>, "
          "TX_SEARCH_DATA_SHARDS=<n> (docs/distributed.md)")
    return 0
