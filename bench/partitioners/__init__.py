"""One module per initial partitioner, named by a configuration's
``partitioner``: ``partition(svc, graph, config, seed)``."""
