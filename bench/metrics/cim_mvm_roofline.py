"""The Pallas bit-serial MVM's share of its roofline, in percent."""

from layerlib import cim_mvm_roofline as read  # noqa: F401
