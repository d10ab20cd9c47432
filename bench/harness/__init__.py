"""The serving benchmark's harness: spec loading, traffic, weights, the
plain reference, trace reduction and the run itself.

Everything that belongs to one configuration, traffic mix, per-layer
metric or cell limit lives in a data file (or, for a metric, a small
reader) of its own under ``bench/``; these modules find each by name.
"""
