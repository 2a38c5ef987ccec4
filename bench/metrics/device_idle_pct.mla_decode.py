"""Share of the traced window in which the device ran nothing, in the MLA
decode cell; read as ``device_idle_pct.decode`` reads the K/V cell's."""
import byname

read = byname.load("metrics", "device_idle_pct.decode").read
