"""Shared pieces of the on-chip benchmark: finding cells by name, the
traffic drivers, the open-loop arithmetic, the trace reduction, the peaks
table and the byte model. Nothing here is specific to one configuration,
traffic mix or metric; those live in files of their own under ``bench/``.
"""
