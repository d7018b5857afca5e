"""The chip benchmark's shared code: set-up, generators, required work,
trace reduction, the plain references and the two drivers (training and
serving). Everything that belongs to one configuration, cell, traffic mix
or per-layer metric lives in a data file or reader of its own beside this
package, found by the name ``BENCHMARK.json`` gives it."""
