"""The repo's benchmark: cells named in BENCHMARK.json, run one at a time
by run.py. Everything that decides a number lives in this directory;
from the program it takes only the system under test and its counters.
"""
