"""The system under test: one ``SVFusionEngine`` in the three-tier mode,
built from a deployment file (``bench/configs/<config>.json``) and the
cell's lane (``pq``: the PQ lane with exact re-rank; otherwise the exact
tiered lane)."""


def engine_config(config: dict, lane: str, capacity: int, disk_path: str,
                  seed: int):
    from repro.core.engine import EngineConfig
    from repro.core.filters import AttributeSchema
    from repro.core.types import SearchParams
    g, a = config["guarantees"], config["attributes"]
    return EngineConfig(
        degree=config["degree"], cache_slots=config["cache_slots"],
        capacity=capacity, disk_path=disk_path, disk_capacity=capacity,
        search=SearchParams(k=config["k"], pool=config["pool"],
                            max_iters=config["max_iters"],
                            beam=config["beam"]),
        seed=seed % (2 ** 31 - 1),
        attributes=AttributeSchema(tag_fields=tuple(a["tag_fields"]),
                                   num_fields=tuple(a["num_fields"]),
                                   tag_domain=a["tag_domain"]),
        filter_fallback_selectivity=config["filter_fallback_selectivity"],
        cache_dtype=config["cache_dtype"], pq_enabled=(lane == "pq"),
        pq_m=config["pq_m"], pq_bits=config["pq_bits"],
        rerank_depth=config["rerank_depth"],
        build_partitions=config["build_partitions"],
        build_cross_samples=config["build_cross_samples"],
        wal_enabled=g["wal"], wal_group_commit=g["wal_group_commit"],
        sync=g["sync"], slo_target_p99=g["slo_target_p99"],
        coalesce_max_batch=config["max_batch"])


def build(config, lane, capacity, disk_path, seed, base, attrs):
    """The engine over ``base`` (ids 0..n0-1, attributes ``attrs``), with
    room for ``capacity`` ids and its disk tier under ``disk_path``."""
    from repro.core.engine import SVFusionEngine
    return SVFusionEngine(base, engine_config(config, lane, capacity,
                                              disk_path, seed),
                          init_attrs=attrs)
