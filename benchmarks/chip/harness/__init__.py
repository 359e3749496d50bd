"""The chip benchmark's harness: launcher, load generator, metric arithmetic,
trace reduction, plain references and the table of peaks.  See ../README.md."""
