"""What does a cached latent row's rounding cost one layer of latent attention?
One layer of Kimi-K2.7-Code's attention at the published widths in numpy
(seeded normal(0, 0.02) matrices, as the benchmark's weights; 257 positions,
the last one's output through every head's values), the cached rows rounded
each way, and the relative error of the attention's output against float32:

    python hack/latent_rounding_model.py [seed] [softmax factor, default 2.00474]

On the CPU, a second a run; no chip, no program code. PR 53 priced every form
of the row with it before a chip call, and the probe's decode readings
followed it to a tenth (PERF.md section 6): the plain int8 row 1.15%, of
which the rotated key's rounding alone 0.8 (it is 93% of the scores' spread
under this draw); the key's second code 0.81; a scale a 128 channels of the
latent besides 0.72; bfloat16 rows 0.33. The rotation is left out: it is
orthogonal and changes no dot product."""
import numpy as np, sys
rng=np.random.default_rng(int(sys.argv[1]) if len(sys.argv)>1 else 0)
D,H,C,dn,dr,dv,Rq=7168,64,512,128,64,128,1536
T=257
f32=np.float32
def nrm(*s): return (rng.standard_normal(s)*0.02).astype(f32)
wq_a,wq_b,wkv_a=nrm(D,Rq),nrm(Rq,H*(dn+dr)),nrm(D,C+dr)
w_uk,w_uv=nrm(H,dn,C),nrm(H,C,dv)
def rms(x): return x/np.sqrt((x*x).mean(-1,keepdims=True)+1e-5)
u=rms(rng.standard_normal((T,D)).astype(f32))
cq=rms(u@wq_a); q=(cq[-1]@wq_b).reshape(H,dn+dr); qn,qr=q[:,:dn],q[:,dn:]
kv=u@wkv_a; c=rms(kv[:,:C]); kr=kv[:,C:]   # rotation is orthogonal: skip
scale=(dn+dr)**-0.5*float(sys.argv[2]) if len(sys.argv)>2 else (dn+dr)**-0.5*2.00474
q_abs=np.einsum('hn,hnc->hc',qn,w_uk)
def attend(c_k,kr_k,c_v):
    s=(q_abs@c_k.T+qr@kr_k.T)*scale
    s=s-s.max(-1,keepdims=True); p=np.exp(s); p/=p.sum(-1,keepdims=True)
    o=p@c_v
    return np.einsum('hc,hcv->hv',o,w_uv).reshape(-1)
ref=attend(c,kr,c)
def q8(x,G=1):
    sh=x.shape; x=x.reshape(sh[0],G,-1)
    s=np.abs(x).max(-1,keepdims=True)/127
    return (np.round(x/s)*s).reshape(sh)
def q16(x):
    s=np.abs(x).max(-1,keepdims=True)/127
    y=x/s; hi=np.round(y); lo=np.round((y-hi)*254)
    return (hi+lo/254)*s
def bf(x):
    import ml_dtypes
    return x.astype(ml_dtypes.bfloat16).astype(f32)
def err(o): return 100*np.linalg.norm(o-ref)/np.linalg.norm(ref)
res={}
res['int8 now']=err(attend(q8(c),q8(kr),q8(c)))
res['kr only q8']=err(attend(c,q8(kr),c))
res['lat-key only q8']=err(attend(q8(c),kr,c))
res['val only q8']=err(attend(c,kr,q8(c)))
res['kr16']=err(attend(q8(c),q16(kr),q8(c)))
for G in (2,4,8,16,32):
    res[f'kr16+G{G}']=err(attend(q8(c,G),q16(kr),q8(c,G)))
    res[f'kr8+G{G}']=err(attend(q8(c,G),q8(kr),q8(c,G)))
res['bf16 rows']=err(attend(bf(c),bf(kr),bf(c)))
s=(q_abs@c.T+qr@kr.T)*scale
print('score std',s.std(), 'rope part std',(qr@kr.T*scale).std())
for k,v in res.items(): print(f'{k:18s} {v:.3f}')
