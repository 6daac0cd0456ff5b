"""Layer "device memory": the measured peak over what
``pmesh.memory_plan`` prices for the configuration's sizes.  The plan
is a model (of the fused pipeline): it is reported against the
measurement, never in its place."""


def read(ctx):
    from nbodykit_tpu.pmesh import memory_plan
    if not ctx.get('peak_bytes'):
        return None
    c = ctx['config']
    plan = memory_plan(c['Nmesh'], c['N'], ndevices=ctx['chips'])
    return ctx['peak_bytes'] / float(plan['peak_bytes'])
