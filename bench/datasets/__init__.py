"""One module per dataset, named by a configuration's ``dataset``: its graph
(``build``), its access pattern (``logs``), its plain reference
(``Reference``, with ``control=True`` for the control) and ``TINY``, the
configuration overrides at which ``build`` still makes a valid graph on the
CPU (the tests rehearse every cell of the dataset at that size)."""
