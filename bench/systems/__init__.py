"""One driver per kind of configuration, named by its ``system`` key."""
