"""The serving benchmark's harness: spec loading, traffic, the model
families (weights, the plain reference, operation counts), trace
reduction and the run itself.

Everything that belongs to one configuration, model family, traffic
mix, per-layer metric or cell limit lives in a file of its own under
``bench/`` (a data file, a family module or a small reader); these
modules find each by name.
"""
