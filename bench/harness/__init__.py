"""The benchmark's harness: the yardstick that later changes to the
program are measured with."""
