"""One module per dataset, named by a configuration's ``dataset``: its graph
(``build``), its access pattern (``logs``) and its plain reference
(``Reference``, with ``control=True`` for the control)."""
