#!/usr/bin/env python3
"""The control of the comparison: the plain reference put in the program's
place, computed one precision below what the configuration states (float32
database hashes with no float64 repair; bfloat16 operands for the query
hashes and the distances). It has to come out as not correct; its numbers
are the upper readings the limits are set below.

    python3 benchmarks/e2lsh/control.py --config sift300k-hbm \
        --seeds 11 12 13

For each seed: the cell's data and hash family, a sample of pool queries as
large as a run compares, drawn from the seed, and the compared numbers of
the control against the reference. Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))


def control_readings(config: dict, seed: int) -> dict:
    import numpy as np

    import compare
    import datagen
    import reference

    spec = dict(config["dataset"], n=config["n"])
    data = datagen.make_data(spec, seed)
    ix = config["index"]
    family = datagen.make_family(ix, spec["d"], seed)
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 4]))
    ids = rng.integers(0, len(data.pool), size=config["compare"]["sample"])
    uniq, inv = np.unique(ids, return_inverse=True)
    qs = data.pool[uniq]
    want = reference.search(data.db, qs, family, ix)
    ctl = reference.search(data.db, qs, family, ix, precision="control")
    got = {k: v[inv] for k, v in compare.as_fields(ctl).items()}
    want = reference.Answers(**{f: getattr(want, f)[inv]
                                for f in reference.Answers.__dataclass_fields__})
    return compare.compare(data.db, data.pool[ids], got, want, 0,
                           config["compare"]["limits"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE.parents[1] / "src"))
    import jax
    config = json.loads((HERE / "configs" / f"{args.config}.json").read_text())
    dev = jax.devices()[0]
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = control_readings(config, seed)
        print(json.dumps(dict(config=args.config, seed=seed,
                              device=dev.device_kind,
                              seconds=time.perf_counter() - t0, **out)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
