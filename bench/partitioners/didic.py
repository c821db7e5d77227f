"""DiDiC from a random start (``RuntimePartitioner.initial``), for the
configuration's ``didic_iterations``; the start is drawn from the run's
partition stream."""


def partition(svc, graph, config: dict, seed: int) -> None:
    svc.partition_didic(seed=seed)
