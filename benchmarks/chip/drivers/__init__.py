"""One module per kind of system a configuration runs (named by its
``driver`` key): it builds the system, serves the mix and checks it."""
