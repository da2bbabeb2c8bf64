"""The benchmark's own code: roles, data, estimators, trace reduction,
discovery of the data files. Nothing here is imported by the program,
and nothing here imports more of the program than the system under
test (its client API, its counters and its role entry point)."""
