"""The paper's hardcoded GIS partitioning: ``k`` longitude bands of equal
vertex counts (``partitioners.hardcoded_gis``). Set-up pays no DiDiC."""


def partition(svc, graph, config: dict, seed: int) -> None:
    from repro.core import partitioners

    svc.partition_with(partitioners.hardcoded_gis(graph, config["k"]))
