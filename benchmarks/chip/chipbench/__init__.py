"""The chip benchmark's own code: loading cells by name, traffic, weights,
the plain reference, operation counts, trace reduction and the timed run.

Nothing here is imported by the program; the program is imported from
``src/`` only by ``engine_run`` (the system under test)."""
