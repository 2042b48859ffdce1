"""Serving (KFServing parity): model export, servers, InferenceService.

Nothing is re-exported here: the control plane imports this package's
router/autoscaler/storage modules and must stay free of JAX (a chip has one
owner, and it is the replica, not the plane).
"""
