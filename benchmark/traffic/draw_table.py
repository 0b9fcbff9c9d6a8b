"""Draws a closed-loop replay table ONCE and writes it into its traffic file.

    python3 benchmark/traffic/draw_table.py <path/to/traffic/name.json>

A traffic file of kind ``serve_closed_replay`` states its distribution
(``prompt_tokens``, ``output_tokens``), how many clients and requests a
client (``table``) and the ``generator_seed``; this script fills in
``clients`` (for every client an ordered list of [prompt tokens, output
tokens]) and ``drawn`` (what came out). The benchmark reads the committed
table and never draws: ``--seed`` of a run does not reach it, so every run
of a cell replays the same requests in the same order. A later PR adds a
mix by writing a new file with its parameters and running this on it.
"""

import json
import sys

import numpy as np


def draw(rng, spec, n):
    if spec["dist"] == "lognormal":
        v = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
    elif spec["dist"] == "uniform":
        v = rng.uniform(spec["min"], spec["max"] + 1, n)
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return np.clip(v.astype(np.int64), spec["min"], spec["max"])


def draw_clients(mix: dict):
    """The table of a mix: a function of its recorded parameters alone."""
    rng = np.random.default_rng(mix["generator_seed"])
    clients = []
    for _ in range(mix["table"]["clients"]):
        n = mix["table"]["requests_per_client"]
        p = draw(rng, mix["prompt_tokens"], n)
        o = draw(rng, mix["output_tokens"], n)
        clients.append([[int(a), int(b)] for a, b in zip(p, o)])
    return clients


def summary(clients) -> dict:
    flat_p = [a for c in clients for a, _ in c]
    flat_o = [b for c in clients for _, b in c]
    return {"prompt_mean": float(np.mean(flat_p)),
            "prompt_median": float(np.median(flat_p)),
            "output_mean": float(np.mean(flat_o)),
            "max_context": int(max(a + b for a, b in zip(flat_p, flat_o)))}


def main(path: str) -> None:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("kind") != "serve_closed_replay":
        raise SystemExit(f"{path}: not a serve_closed_replay mix")
    mix.pop("clients", None)
    mix["drawn"] = summary(draw_clients(mix))
    mix["clients"] = draw_clients(mix)          # the table comes last
    with open(path, "w") as f:
        json.dump(mix, f, separators=(",", ":"))
        f.write("\n")
    print(path, mix["drawn"])


if __name__ == "__main__":
    main(sys.argv[1])
