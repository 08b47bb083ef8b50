"""The system under test, built from a configuration through the program's
own entry points: ``solve_params`` (the paper's Eq. 5), the index build,
placement in HBM or a spill to local disk served by the block store, a
``SearchEngine`` plan and the ``BatchQueue`` in front of it."""
from __future__ import annotations

import dataclasses
import gc
import os
import time

import numpy as np

from coldcache import drop_page_cache, residency

_PARAM_KEYS = ("m", "L", "S", "r", "u", "block_objs", "fp_bits")


class NotCold(RuntimeError):
    """The host kept the spill in its page cache, so the configuration's
    cold store (misses read from the disk) cannot be run here."""


@dataclasses.dataclass
class System:
    queue: object             # repro.serving.BatchQueue, not started
    engine: object            # repro.core.SearchEngine
    external: object          # repro.storage.ExternalIndex, or None
    setup: dict               # seconds by phase
    info: dict
    spill_path: str = ""

    def close(self) -> None:
        if self.external is not None:
            self.external.close()
        self.queue = self.engine = self.external = None
        gc.collect()
        if self.spill_path and os.path.exists(self.spill_path):
            os.remove(self.spill_path)


def resolve_params(config: dict, db: np.ndarray):
    """The program's parameter solve, checked against the numbers the
    configuration states (so a change to the solve cannot move the cell)."""
    from repro.core.probabilities import solve_params
    ix = config["index"]
    p = solve_params(db.shape[0], db.shape[1], c=ix["c"], w=ix["w"],
                     gamma=ix["gamma"], x_max=float(np.abs(db).max()),
                     max_L=ix["max_L"], block_bytes=ix["block_bytes"])
    got = {k: int(getattr(p, k)) for k in _PARAM_KEYS}
    want = {k: int(ix[k]) for k in _PARAM_KEYS}
    if got != want:
        raise RuntimeError(f"parameter solve gives {got}, the configuration "
                           f"states {want}")
    return p


def build(config: dict, data, family, workdir: str, log) -> System:
    import jax
    from repro.core import E2LSHoS, HashFamily, SearchEngine
    from repro.core.index import build_host_index
    from repro.serving import BatchQueue

    setup, info = {}, {}
    params = resolve_params(config, data.db)
    fam = HashFamily(a=family.a, b=family.b, rm=family.rm, w=params.w,
                     u=params.u, fp_bits=params.fp_bits)
    t0 = time.perf_counter()
    index = build_host_index(data.db, params, family=fam)
    setup["build_s"] = time.perf_counter() - t0
    info["index_bytes"] = int(sum(
        np.asarray(getattr(index.arrays, f)).nbytes
        for f in index.arrays.array_fields()))
    tier = config["tier"]
    external, spill_path = None, ""
    t0 = time.perf_counter()
    if tier == "hbm":
        arrays = jax.device_put(index.arrays)
        jax.block_until_ready(arrays)
        index = dataclasses.replace(index, arrays=arrays)
        engine = SearchEngine(E2LSHoS(index))
        setup["placement_s"] = time.perf_counter() - t0
    elif tier == "ssd":
        from repro.storage import load_external
        os.makedirs(workdir, exist_ok=True)
        spill_path = os.path.join(workdir, f"{config['name']}.e2l")
        index.spill(spill_path)
        del index
        gc.collect()
        setup["spill_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        st = config["store"]
        external = load_external(spill_path, backend=st["backend"],
                                 qd=st["qd"], strict=True)
        engine = SearchEngine(external)
        info["dropped_page_cache"] = drop_page_cache(spill_path)
        info["page_cache_resident"] = resident = residency(spill_path)
        limit = st.get("cold_max_resident")
        if limit is not None and not resident <= limit:
            external.close()
            os.remove(spill_path)
            raise NotCold(f"{resident!r} of the spill is still in the page "
                          f"cache after the drop, the configuration allows "
                          f"{limit!r}: block-store misses would not reach "
                          f"the disk")
        info["spill_bytes"] = os.path.getsize(spill_path)
        info["store_cache_rows"] = int(external.store.cache_rows)
        info["store_rows"] = int(external.store.nb)
        setup["placement_s"] = time.perf_counter() - t0
    else:
        raise ValueError(f"unknown tier {tier!r}")
    q = config["queue"]
    queue = BatchQueue(engine, plan=config["plan"], k=config["index"]["k"],
                       ladder=tuple(q["ladder"]), tick_us=q["tick_us"],
                       warmup=False)
    chain = int(queue.cfg.max_chain)
    if chain != int(config["index"]["max_chain"]):
        raise RuntimeError(f"plan walks {chain} chunks per bucket, the "
                           f"configuration states {config['index']['max_chain']}")
    info["ladder"] = list(queue.ladder)
    log(f"[system] tier={tier} plan={config['plan']} "
        f"params={ {k: getattr(params, k) for k in _PARAM_KEYS} } "
        f"radii={len(params.radii)} info={info}")
    return System(queue=queue, engine=engine, external=external, setup=setup,
                  info=info, spill_path=spill_path)
