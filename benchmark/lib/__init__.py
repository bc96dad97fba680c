"""The benchmark's own yardstick.  Only ``program.py`` imports the program
(it turns the benchmark's files into the program's objects); everything
else here is independent of it."""
