"""The chip benchmark's own code: cell specs, inputs, the plain reference,
the correctness comparison, the profiler-trace reduction and the analytic
FLOP counts. Only ``harness`` (which drives the program's entry), the
family modules ``families/<family>.py`` (which build the program's
configuration objects) and ``faults`` (which plants faults in it for the
limit readings and tests) import the program."""
