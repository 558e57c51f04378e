"""The benchmark's own harness: everything the yardstick needs that is
not one configuration's, one traffic mix's or one metric's.  Nothing
here imports ``learningorchestra_tpu`` except :mod:`lobench.rest`, which
boots the system under test."""
