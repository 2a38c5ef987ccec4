"""Device time of one decode step over the MLA layers' compressed latent
caches: the device time inside the ``bench.step`` spans (every layer's
projections, append with its flush, attention), per step; read as
``kv_step_device_ms`` reads the K/V cell's."""
import byname

read = byname.load("metrics", "kv_step_device_ms").read
