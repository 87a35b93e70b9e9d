"""The benchmark's own code: general machinery only. Whatever belongs to one
configuration, one traffic mix, one circuit or one metric sits in a file of
its own (configs/, traffic/, requests/, reference/, servers/, metrics/),
found by the name BENCHMARK.json gives."""
