"""The chip benchmark: open-loop cells of the streaming pipelines on a TPU.

``run.py`` is the one command. Everything that belongs to one
configuration, traffic mix, processor kind or metric sits in a file of its
own under this directory and is found by the name ``BENCHMARK.json`` gives
it, so a new cell is new files plus new entries there.
"""
